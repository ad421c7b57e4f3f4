"""Elastic rescale end-to-end: save a sharded train state under one mesh,
restore it under a DIFFERENT mesh (fewer devices), continue training, and
verify the loss trajectory matches an uninterrupted run bit-for-bit.

Runs in a subprocess with 8 forced host devices (the test process itself
keeps 1 device; see dryrun.py's device-count note).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.checkpoint import checkpoint as ckpt
    from repro.configs import base
    from repro.data.pipeline import ShardedLoader, TokenTaskConfig
    from repro.distributed.fault_tolerance import ElasticPlan
    from repro.models import transformer as T
    from repro.optim import adamw

    base.load_all()
    cfg = base.reduce_for_smoke(base.get("yi-9b"))
    ocfg = adamw.AdamWConfig(lr=1e-3)
    data = ShardedLoader("token", TokenTaskConfig(vocab=cfg.vocab),
                         batch=8, seq_len=32)

    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.lm_loss(p, cfg, batch))(params)
        params, opt, _ = adamw.update(grads, opt, params, ocfg)
        return params, opt, loss

    def run(params, opt, mesh, lo, hi):
        dp = NamedSharding(mesh, P("data", None))
        losses = []
        with mesh:
            jstep = jax.jit(step_fn)
            for s in range(lo, hi):
                toks, tgts = data.get(s)
                batch = {"tokens": jax.device_put(toks, dp),
                         "targets": jax.device_put(tgts, dp)}
                params, opt, loss = jstep(params, opt, batch)
                losses.append(float(loss))
        return params, opt, losses

    def put(tree, mesh):
        rep = NamedSharding(mesh, P())
        return jax.tree.map(lambda x: jax.device_put(np.asarray(x), rep), tree)

    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params, ocfg)

    # --- reference: 6 uninterrupted steps on the BIG mesh (8 devices) ---
    mesh8 = jax.make_mesh((8,), ("data",), devices=jax.devices()[:8],
                          axis_types=(AxisType.Auto,))
    p_ref, o_ref, losses_ref = run(put(params, mesh8), put(opt, mesh8),
                                   mesh8, 0, 6)

    # --- elastic: 3 steps on 8 devices, checkpoint, RESTORE ON 4, 3 more ---
    plan = ElasticPlan(old_shape=(8, 1), new_hosts=1, chips_per_host=4)
    assert plan.needs_reshard
    p1, o1, losses_a = run(put(params, mesh8), put(opt, mesh8), mesh8, 0, 3)
    ckpt.save(sys.argv[1], 3, (p1, o1), {"step": 3})

    mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                          axis_types=(AxisType.Auto,))
    like = (p1, o1)
    rep4 = jax.tree.map(
        lambda x: NamedSharding(mesh4, P()), like)
    p2, o2 = ckpt.restore(sys.argv[1], 3, like, shardings=rep4)
    data.reshard(shard=0, n_shards=1)  # deterministic stream continues
    _, _, losses_b = run(p2, o2, mesh4, 3, 6)

    got = losses_a + losses_b
    np.testing.assert_allclose(got, losses_ref, rtol=2e-4, atol=2e-4)
    print("ELASTIC_OK", got)
""")


@pytest.mark.slow
def test_elastic_rescale_roundtrip(tmp_path):
    # the child forces 8 host devices and must never reach for a chip
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path / "ckpt")],
                         cwd=Path(__file__).resolve().parents[1],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=1200)
    assert "ELASTIC_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]
