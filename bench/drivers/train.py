"""Data-parallel training steps through `train.opera_dp`, as
`launch/train.py` builds them: one replica per chip on a `data` mesh over
every chip, the gradients summed by the rotor reduce-scatter/all-gather
of `core/collectives.py`.

Set-up makes the weights and the token rows on the device from the seed
(`bench/ref/llama.py`), builds the compiled step with its state, and
drives it through its first `check_steps` steps on rows that all differ:
the readings `correct` compares.  The window then calls the same step on
the same state.  A call is one step of global batch x sequence tokens.
After the window the chips' copies of the parameters are compared, and
the reference repeats the first steps in float32.
"""
from __future__ import annotations

import collections
import functools

import numpy as np

from bench.ref import llama as ref

RATE_METRIC = "train_tokens_per_s"
# the program's parameter tree, by path, and the reference's name for it
PROGRAM_LEAVES = {
    ("embed",): "embed",
    ("final_norm", "scale"): "final_norm",
    **{("stack", "blocks", "0", *p): n for p, n in {
        ("ln1", "scale"): "ln1", ("ln2", "scale"): "ln2",
        ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
        ("attn", "wo"): "wo", ("ffn", "w_gate"): "w_gate",
        ("ffn", "w_up"): "w_up", ("ffn", "w_down"): "w_down"}.items()},
}


def program_norm_eps(mcfg) -> float:
    """The epsilon the program's RMSNorms run: its configuration's
    `norm_eps` where it carries one, else the 1e-6 that
    `models/layers.apply_norm` fixes, which no caller overrides."""
    return getattr(mcfg, "norm_eps", 1e-6)


def _path(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def to_program(w: dict, shapes):
    import jax

    def leaf(path, s):
        name = PROGRAM_LEAVES.get(_path(path))
        if name is None or tuple(w[name].shape) != tuple(s.shape):
            raise ValueError(f"program parameter {_path(path)} {s.shape} "
                             "has no counterpart in the reference")
        return w[name]

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def from_program(tree) -> dict:
    import jax

    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[PROGRAM_LEAVES[_path(path)]] = x
    return out


def worst_leaf(got: dict, want: dict, moved: dict) -> float:
    """Largest gap between a leaf's norm in the program and in the
    reference, over the larger of the reference's norm and the median
    leaf's; leaves whose reference gradient (`moved`) is under a
    thousandth of the median leaf's move by round-off alone and are left
    out."""
    g = np.concatenate([got[k] for k in sorted(want)])
    w = np.concatenate([want[k] for k in sorted(want)])
    m = np.concatenate([moved[k] for k in sorted(want)])
    keep = m >= 1e-3 * np.median(m)
    return float(np.max(np.abs(g - w)[keep]
                        / np.maximum(w, np.median(w))[keep]))


def replica_gap(mesh, tree) -> float:
    """Largest difference between a chip's copy of a replicated leaf and
    the first chip's, over the leaf's largest magnitude there.  Every chip
    applies the same summed gradient, so sound data parallelism keeps the
    copies equal bit for bit."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def per_chip(t):
        first = lax.axis_index(axis) == 0
        gaps = [jnp.max(jnp.abs(x - lax.psum(jnp.where(first, x, 0), axis)))
                / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                for x in jax.tree.leaves(t)]
        return lax.pmax(jnp.max(jnp.stack(gaps)), axis)

    return float(jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False))(tree))


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs import get_config
        from repro.launch.mesh import make_host_mesh, pctx_for_mesh
        from repro.models.model import param_shapes
        from repro.models.sharding import batch_spec
        from repro.optim.adamw import AdamWConfig
        from repro.train.opera_dp import (init_opera_dp_state,
                                          make_opera_dp_train_step)

        if traffic["check_steps"] >= traffic["warmup_steps"]:
            raise ValueError("the reference follows warm-up steps only")
        self.cfg, self.job, self.seed = cfg, traffic, seed
        mcfg = get_config(cfg["program_config"])
        for mine, theirs in (("hidden_size", "d_model"),
                             ("num_hidden_layers", "num_layers"),
                             ("num_attention_heads", "num_heads"),
                             ("num_key_value_heads", "num_kv_heads"),
                             ("head_dim", "head_dim_"),
                             ("intermediate_size", "d_ff"),
                             ("vocab_size", "vocab_size"),
                             ("rope_theta", "rope_theta")):
            if cfg[mine] != getattr(mcfg, theirs):
                raise ValueError(f"{mine} {cfg[mine]} != program's "
                                 f"{theirs} {getattr(mcfg, theirs)}")
        # checked here, before minutes of compiling: at its limits
        # `correct` cannot see even a tenfold epsilon
        eps = program_norm_eps(mcfg)
        if cfg["rms_norm_eps"] != eps:
            raise ValueError(f"rms_norm_eps {cfg['rms_norm_eps']} != the "
                             f"program's RMSNorm epsilon {eps}")
        self.mesh = make_host_mesh(model=1)
        if len(self.mesh.devices.flat) != len(devices):
            raise ValueError(f"mesh of {self.mesh.devices.size} devices, "
                             f"the cell has {len(devices)}")
        pctx = pctx_for_mesh(self.mesh)
        opt = AdamWConfig(**{k: traffic[k] for k in (
            "lr", "beta1", "beta2", "eps", "weight_decay", "clip_norm",
            "warmup_steps", "total_steps")})
        self.rep = NamedSharding(self.mesh, P())
        w0 = ref.make_weights(cfg, seed, self.rep)
        state = jax.device_put(
            init_opera_dp_state(to_program(w0, param_shapes(mcfg))), self.rep)
        del w0
        self.jitted = jax.jit(
            make_opera_dp_train_step(mcfg, pctx, opt),
            out_shardings=(jax.tree.map(lambda x: x.sharding, state), None))
        self.state = state
        n = len(devices)
        self.rows = traffic["per_chip_batch"] * n
        seq = traffic["seq_len"]
        shape = (self.rows, seq)
        spec = {k: NamedSharding(self.mesh, batch_spec(k, shape, pctx))
                for k in ("tokens", "targets")}

        def make_rows(key):
            t = jax.random.randint(key, (traffic["batches"], self.rows,
                                         seq + 1), 0, cfg["vocab_size"])
            return [dict(tokens=t[i, :, :-1], targets=t[i, :, 1:])
                    for i in range(traffic["batches"])]

        self.batches = jax.jit(make_rows, out_shardings=[
            spec] * traffic["batches"])(jax.random.key((seed + 1) % 2**63))
        self.work = self.rows * seq
        self.backend = f"opera-dp over {n} chips"
        self.describe = (f"{cfg['name']} {n} chips x {traffic['per_chip_batch']}"
                         f" x {seq} tokens, {self.backend}")
        self.in_flight = collections.deque()

    def _step(self, batch):
        import jax

        with jax.set_mesh(self.mesh):
            self.state, metrics = self.jitted(self.state, batch)
        return metrics

    def warm(self):
        """The first steps, on rows that all differ, with the readings the
        reference is held to: each step's loss and global gradient norm
        before clipping, the first gradient as the optimizer took it (its
        first moment over 1 - beta1) and the parameters' change after the
        last of them."""
        b1 = self.job["beta1"]
        self.losses, self.gnorms = [], []
        for i in range(self.job["check_steps"]):
            metrics = self._step(self.batches[i])
            self.losses.append(float(metrics["loss"]))
            self.gnorms.append(float(metrics["grad_norm"]))
            if i == 0:
                m = from_program(self.state["opt"]["m"])
                self.g1 = {k: v / (1 - b1) for k, v in
                           ref.leaf_norms(m).items()}
        w0 = ref.make_weights(self.cfg, self.seed, self.rep)
        self.dw = ref.leaf_norms(from_program(self.state["params"]), w0)

    def call(self, i: int):
        """One step; at most two run ahead of the host."""
        k = self.job["check_steps"] + i
        self.in_flight.append(
            self._step(self.batches[k % len(self.batches)])["loss"])
        if len(self.in_flight) > 2:
            self.in_flight.popleft().block_until_ready()

    def wait(self):
        while self.in_flight:
            self.in_flight.popleft().block_until_ready()

    def release(self):
        """Reads how far the chips' copies of the parameters drifted
        apart, then frees the program's state."""
        self.replicas = replica_gap(self.mesh, self.state["params"])
        self.state = self.jitted = None

    def reference(self, precision: str = "exact", rows_per_chip=None,
                  exchange: bool = True):
        """The first steps again in the reference, from the same weights
        and rows: losses, first clipped gradient and change, by leaf (on
        the first chip, where the chips differ)."""
        import jax

        job = self.job
        grad = ref.make_grad_fn(self.cfg, job, self.mesh, "data", precision,
                                rows_per_chip or job["per_chip_batch"],
                                exchange)
        w = ref.make_weights(self.cfg, self.seed, self.rep)
        w0 = w
        m = jax.tree.map(jax.numpy.zeros_like, w)
        v = jax.tree.map(jax.numpy.zeros_like, w)
        step = jax.jit(functools.partial(ref.adamw, job), static_argnums=4)
        losses, gnorms, g1 = [], [], None
        for i in range(job["check_steps"]):
            b = self.batches[i]
            ce, g = grad(w, b["tokens"], b["targets"])
            losses.append(float(ce))
            gnorms.append(float(np.sqrt(sum(
                np.sum(n * n) for n in ref.leaf_norms(g).values()))))
            w, m, v, g = step(w, g, m, v, i)
            if i == 0:
                g1 = ref.leaf_norms(g)
        dw = ref.leaf_norms(w, w0)
        return dict(losses=losses, gnorms=gnorms, g1=g1, dw=dw)

    def readings(self) -> dict:
        return dict(losses=self.losses, gnorms=self.gnorms, g1=self.g1,
                    dw=self.dw)

    @staticmethod
    def gaps(got: dict, want: dict) -> dict:
        return dict(
            loss_gap=float(np.max(np.abs(np.subtract(got["losses"],
                                                     want["losses"])))),
            gnorm_gap=float(np.max(np.abs(np.subtract(
                got["gnorms"], want["gnorms"])) / np.asarray(want["gnorms"]))),
            grad_gap=worst_leaf(got["g1"], want["g1"], want["g1"]),
            update_gap=worst_leaf(got["dw"], want["dw"], want["g1"]),
        )

    def check(self) -> dict:
        return dict(self.gaps(self.readings(), self.reference()),
                    replica_gap=self.replicas)

    def calibrate(self, faults: bool) -> dict:
        """Readings for the limits: the program against the reference
        and, with `faults`, the control (the reference in fp8) and two
        faults planted in the reference: half of each chip's rows left
        out, and the exchange between chips left out."""
        got = self.readings()
        self.release()
        want = self.reference()
        out = dict(program=dict(self.gaps(got, want),
                                replica_gap=self.replicas))
        if faults:
            out["control"] = self.gaps(self.reference("fp8"), want)
            out["half_batch"] = self.gaps(self.reference(
                rows_per_chip=self.job["per_chip_batch"] // 2), want)
            out["no_exchange"] = self.gaps(self.reference(exchange=False),
                                           want)
        return out

    def guarantees(self) -> dict:
        return {}
