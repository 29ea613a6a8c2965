"""What every driver of the program's train step shares.

A driver is the benchmark's only contact with the program: it builds the
train step that ``repro.launch.train`` builds for the cell's layout, puts the
benchmark's own weights into the program's storage layout, places batches,
and reads per-leaf norms back out of the program's state.  The program
lives in ``src/`` of the checkout.
"""
from __future__ import annotations

import sys

from bench.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
import numpy as np                                     # noqa: E402
from jax.sharding import NamedSharding                 # noqa: E402
from jax.sharding import PartitionSpec as P            # noqa: E402

from repro.models.common import ModelConfig            # noqa: E402
from repro.optim.adam import AdamConfig                # noqa: E402

# program parameter path -> the benchmark's (and the reference's) leaf name
_LEAF = {("ln1", "scale"): "ln1_scale", ("ln1", "bias"): "ln1_bias",
         ("ln2", "scale"): "ln2_scale", ("ln2", "bias"): "ln2_bias",
         ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
         ("attn", "wo"): "wo", ("mlp", "w_up"): "w_up",
         ("mlp", "w_down"): "w_down",
         ("final_norm", "scale"): "final_scale",
         ("final_norm", "bias"): "final_bias",
         ("embed",): "embed", ("head",): "head"}


def model_config(cfg: dict) -> ModelConfig:
    """The program's ModelConfig for a configuration file."""
    prec = cfg["precision"]
    return ModelConfig(
        name=cfg["name"], arch_type=cfg["arch_type"],
        num_layers=cfg["num_layers"], d_model=cfg["d_model"],
        num_heads=cfg["num_heads"], num_kv_heads=cfg["num_kv_heads"],
        d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], hidden_act=cfg["hidden_act"],
        glu=cfg["glu"], norm=cfg["norm"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_embeddings"], dtype=prec["compute"],
        param_dtype=prec["master"], kernels=True)


def adam_config(cfg: dict) -> AdamConfig:
    return AdamConfig(**cfg["optimizer"],
                      moment_dtype=cfg["precision"]["moments"])


def to_program(w: dict) -> dict:
    """The benchmark's weight tree -> the program's parameter tree."""
    lw = w["layers"]
    return {
        "embed": w["embed"], "head": w["head"], "shared": {},
        "final_norm": {"scale": w["final_scale"], "bias": w["final_bias"]},
        "layers": {
            "ln1": {"scale": lw["ln1_scale"], "bias": lw["ln1_bias"]},
            "ln2": {"scale": lw["ln2_scale"], "bias": lw["ln2_bias"]},
            "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {"w_up": lw["w_up"], "w_down": lw["w_down"]},
        },
    }


def leaf_name(path) -> tuple[str, bool]:
    keys = tuple(getattr(k, "key", None) for k in path)
    layered = keys[0] == "layers"
    return _LEAF[keys[1:] if layered else keys], layered


def shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


class Driver:
    """Set by subclasses: ``mesh``, ``mcfg``, ``opt_cfg``, ``step`` (the
    jitted train step), ``bspecs`` (batch PartitionSpecs), ``build`` (a jitted
    ``key -> storage``), and ``layer_order`` (stored layer stack -> layer
    order, for an array of one value per stored layer)."""

    layer_dims = 1     # leading dims of a stored layer leaf that index layers

    def __init__(self, cell):
        self.cell = cell
        self.cfg = cell.config
        self.mcfg = model_config(self.cfg)
        self.opt_cfg = adam_config(self.cfg)

    # -- state -----------------------------------------------------------
    def init_state(self, key):
        from repro.optim.adam import adam_init
        storage = self.build(key)
        return storage, adam_init(storage,
                                  moment_dtype=self.opt_cfg.moment_dtype)

    def place(self, batch: dict) -> dict:
        sh = shardings(self.mesh, self.bspecs)
        return {k: jax.device_put(v, sh[k]) for k, v in batch.items()}

    def compile(self, storage, opt, batch):
        """The step as the window runs it: lowered for these arguments and
        compiled once (from the persistent cache where it is there)."""
        return self.step.lower(storage, opt, batch).compile()

    # -- reading the program's state --------------------------------------
    def _sq(self, tree):
        """{leaf: per-layer (or scalar) sum of squares} of a storage-shaped
        tree; chunk padding is zero and adds nothing."""
        out = {}
        for path, x in jax.tree_util.tree_leaves_with_path(tree):
            name, layered = leaf_name(path)
            x = jnp.square(x.astype(jnp.float32))
            keep = self.layer_dims if layered else 0
            out[name] = jnp.sum(x, axis=tuple(range(keep, x.ndim)))
        return out

    def _host_norms(self, sq: dict) -> dict:
        out = {}
        for name, v in sq.items():
            v = np.sqrt(np.asarray(v, np.float64))
            if v.ndim == 0:
                out[name] = float(v)
            else:
                v = self.layer_order(v)
                out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        return out

    def grad_norms(self, opt) -> dict:
        """The first step's gradient as the optimizer got it, per leaf,
        from the first moment after one step (mu = (1 - b1) g)."""
        sq = jax.jit(self._sq)(opt["mu"])
        scale = 1.0 / (1.0 - self.opt_cfg.b1)
        return {k: v * scale for k, v in self._host_norms(sq).items()}

    def change_norms(self, storage, key) -> dict:
        """Per-leaf norm of the parameters' change since the start."""
        start = self.build(key)
        sq = jax.jit(lambda a, b: self._sq(jax.tree.map(jnp.subtract, a, b)))(
            storage, start)
        del start
        return self._host_norms(sq)

    def info(self) -> dict:
        return {}
