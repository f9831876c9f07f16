"""Multi-device end-to-end checks on a (pod=2, data=2, model=2) mesh:

1. GSPMD trainer with FSDP+TP shardings == single-device trainer (loss).
2. MoE rotor a2a dispatch == xla all_to_all dispatch == single-device.
3. opera-dp trainer with rotor grad sync == single-device update.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import reduced_config  # noqa: E402
from repro.data.pipeline import SyntheticLM  # noqa: E402
from repro.launch.mesh import auto_mesh, pctx_for_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.model import loss_fn, param_shapes  # noqa: E402
from repro.models.parallel import single_device_ctx  # noqa: E402
from repro.models.sharding import param_shardings  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.train.opera_dp import (  # noqa: E402
    init_opera_dp_state,
    make_opera_dp_train_step,
)
from repro.train.trainer import init_train_state, make_train_step  # noqa: E402

mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)

# ---------------- dense arch: gspmd + opera-dp vs single device ------------
cfg = reduced_config(get_config("smollm-360m")).replace(
    num_layers=2, vocab_size=64, grad_sync="rotor"
)
params = init_params(cfg, jax.random.key(0))
src = SyntheticLM(cfg.vocab_size, 16, 8, seed=0)
batch = jax.tree.map(jnp.asarray, src.batch_at(0))

# single-device reference
s_ref = init_train_state(cfg, params)
s_ref, m_ref = jax.jit(make_train_step(cfg, single_device_ctx(), opt))(
    s_ref, batch
)
ref_loss = float(m_ref["loss"])

# gspmd multi-device (params sharded by rules; batch sharded over dp)
pctx = pctx_for_mesh(mesh, grad_sync="xla")
shardings = param_shardings(param_shapes(cfg), cfg, pctx)
with jax.set_mesh(mesh):
    sh_params = jax.device_put(params, shardings)
    state = init_train_state(cfg, sh_params)
    bsh = jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(("pod", "data")))
        ),
        batch,
    )
    state, m = jax.jit(make_train_step(cfg, pctx, opt))(state, bsh)
assert abs(float(m["loss"]) - ref_loss) < 1e-3, (float(m["loss"]), ref_loss)
print("ok: gspmd multi-device trainer matches single-device loss")

# rotor pod-sync trainer
pctx_r = pctx_for_mesh(mesh, grad_sync="rotor")
with jax.set_mesh(mesh):
    state_r = init_train_state(cfg, jax.device_put(params, shardings))
    state_r, m_r = jax.jit(make_train_step(cfg, pctx_r, opt))(state_r, bsh)
assert abs(float(m_r["loss"]) - ref_loss) < 1e-3
pa = jax.tree.leaves(state["params"])
pb = jax.tree.leaves(state_r["params"])
for x, y in zip(pa, pb):
    np.testing.assert_allclose(np.asarray(x, np.float32),
                               np.asarray(y, np.float32), atol=2e-4, rtol=2e-4)
print("ok: rotor pod-sync trainer matches gspmd updates")

# opera-dp explicit trainer
with jax.set_mesh(mesh):
    s_dp = init_opera_dp_state(params)
    s_dp, m_dp = jax.jit(make_opera_dp_train_step(cfg, pctx_r, opt))(s_dp, batch)
assert abs(float(m_dp["loss"]) - ref_loss) < 1e-3
print("ok: opera-dp explicit trainer matches reference loss")

# ---------------- MoE arch: rotor vs xla dispatch ---------------------------
mcfg = reduced_config(get_config("qwen3-moe-30b-a3b"))
mparams = init_params(mcfg, jax.random.key(1))
msrc = SyntheticLM(mcfg.vocab_size, 16, 8, seed=1)
mbatch = jax.tree.map(jnp.asarray, msrc.batch_at(0))

ref_total, _ = loss_fn(mparams, mbatch, mcfg, single_device_ctx())
losses = {}
for dispatch in ("rotor", "rotor_vlb", "xla"):
    pctx_m = pctx_for_mesh(mesh, moe_dispatch=dispatch)
    mshard = param_shardings(param_shapes(mcfg), mcfg, pctx_m)
    with jax.set_mesh(mesh):
        shp = jax.device_put(mparams, mshard)
        bsh = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, P(("pod", "data")))
            ),
            mbatch,
        )
        total, _ = jax.jit(
            lambda p, b: loss_fn(p, b, mcfg, pctx_m)
        )(shp, bsh)
    losses[dispatch] = float(total)
    print(f"ok: moe dispatch={dispatch} loss={losses[dispatch]:.5f}")

# all dispatch modes must agree with each other exactly (same math)
assert abs(losses["rotor"] - losses["xla"]) < 1e-4
assert abs(losses["rotor_vlb"] - losses["xla"]) < 1e-4
# and with the single-device reference up to capacity-drop differences
# (sharded dispatch has per-shard capacity): allow small drift
assert abs(losses["xla"] - float(ref_total)) < 0.2, (losses, float(ref_total))
print("ALL SHARDED CHECKS PASSED")
