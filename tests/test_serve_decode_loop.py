"""Decode loop: on-device sampling in the fused device-resident scan, and
the decode_attn kernel's per-slot lengths and grid pruning.  The scan's
greedy parity with the step-by-step reference loop (mixed prompt lengths,
EOS mid-batch, refills, kernel route) is in test_serve_paged.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import param as pm
from repro.models.model_zoo import Model
from repro.serve.engine import ServeConfig
from repro.serve.scheduler import Batcher


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    model = Model(cfg)
    params = pm.unwrap(model.init(jax.random.key(0)))
    rng = np.random.default_rng(1)
    requests = [(i, rng.integers(0, cfg.vocab, size=n).tolist())
                for i, n in enumerate([3, 5, 8, 11])]
    return cfg, model, params, requests


def _engine_run(model, params, scfg, requests, max_new, eos_id=None):
    b = Batcher(model, params, scfg, eos_id=eos_id)
    for rid, p in requests:
        b.submit(rid, p)
    return b.run(max_new=max_new)


def test_temperature_sampling_runs(setup):
    """Non-greedy path: on-device categorical sampling yields in-vocab ids
    for every requested token."""
    cfg, model, params, requests = setup
    scfg = ServeConfig(max_len=64, batch=4, dtype=jnp.float32,
                       sync_every=4, temperature=0.8)
    got = _engine_run(model, params, scfg, requests, max_new=6)
    for rid, _ in requests:
        assert len(got[rid]) == 6
        assert all(0 <= t < cfg.vocab for t in got[rid])


def test_per_slot_lengths_and_grid_pruning():
    """decode_attn with per-slot lengths == oracle, with and without the
    statically pruned KV grid (s_cap)."""
    from repro.kernels.decode_attn import decode_attn
    from repro.kernels.decode_attn.ref import decode_attn_ref
    rng = np.random.default_rng(0)
    b, s, hq, hkv, d = 4, 512, 8, 2, 32
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    lengths = jnp.asarray([1, 64, 129, 200], jnp.int32)
    ref = decode_attn_ref(q, k, v, lengths)
    out = decode_attn(q, k, v, lengths, bs=64)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)
    # dead blocks past every slot's length pruned from the grid entirely
    capped = decode_attn(q, k, v, lengths, bs=64, s_cap=256)
    np.testing.assert_allclose(capped, ref, rtol=3e-4, atol=3e-4)
