"""The non-pipelined train step: ``stepfn.build_train_step`` with an
``AccumConfig`` (layered or standard accumulation, ZeRO-partitioned or
replicated storage) on a data x model mesh, as ``repro.launch.train``
builds it."""
from __future__ import annotations

import jax

from bench import spec, weights
from bench.drivers.common import Driver as _Base
from bench.drivers.common import shardings, to_program

from repro import compat
from repro.core import partition as zp
from repro.core import stepfn
from repro.core.accumulation import AccumConfig
from repro.models import transformer as T


class Driver(_Base):
    def __init__(self, cell, devices=None):
        super().__init__(cell)
        t = cell.traffic
        spec.check_keys(t, {"method"}, f"traffic of cell {cell.name!r}")
        m = t["mesh"]
        self.mesh = compat.make_mesh((m.get("data", 1), m.get("model", 1)),
                                     ("data", "model"), devices=devices)
        acc = AccumConfig(method=t["method"], partitioned=t["partitioned"],
                          n_microbatches=t["n_microbatches"])
        self.step = stepfn.build_train_step(self.mcfg, self.mesh, acc,
                                            self.opt_cfg, donate=True)
        axis = stepfn.axis_ctx(self.mesh)
        self.bspecs = stepfn.batch_specs(self.mcfg, axis, microbatched=True)
        self.build = jax.jit(self._builder(axis, t["partitioned"]))

    def _builder(self, axis, partitioned: bool):
        """As ``stepfn.init_storage``, from the benchmark's weights."""
        cfg, mesh = self.cfg, self.mesh
        fspecs = T.param_specs(self.mcfg, axis.tp)
        full = shardings(mesh, fspecs)
        pspecs = zp.partitioned_specs(fspecs)

        def convert(params):
            di = jax.lax.axis_index(axis.data) if axis.data else 0
            return jax.tree_util.tree_map_with_path(
                lambda path, leaf: zp.partition_local(
                    leaf, axis.ndata, di, stacked=zp.is_stacked_path(path)),
                params)

        def build(key):
            params = jax.lax.with_sharding_constraint(
                to_program(weights.make(cfg, key)), full)
            if not partitioned:
                return params
            return compat.shard_map(convert, mesh=mesh, in_specs=(fspecs,),
                                    out_specs=pspecs)(params)

        return build

    @staticmethod
    def layer_order(v):
        return v

    def fused_adamw_params(self, storage) -> int:
        """Partitioned storage takes the fused kernel on every leaf."""
        if not (self.mcfg.kernels and self.cell.traffic["partitioned"]):
            return 0
        return sum(x.size for x in jax.tree.leaves(storage))
