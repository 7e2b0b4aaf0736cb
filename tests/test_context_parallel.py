"""§Perf optimizations stay correct: context-parallel attention equals the
unsharded computation on a real (host-device) mesh, and the fp8 KV cache
decodes finitely."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import build_model
from repro.launch.mesh import _make_mesh

mesh = _make_mesh((2, 4), ("data", "model"))
cfg = get_config("yi-34b").reduced()          # attn_seq_shard=True inherited
assert cfg.attn_seq_shard
model = build_model(cfg)
key = jax.random.key(0)
params = model.init(key)
toks = jax.random.randint(key, (4, 32), 0, cfg.vocab_size, jnp.int32)

plain, _ = model.apply(params, toks)          # no mesh: constraint no-ops
with mesh:
    sharded = jax.jit(
        lambda p, t: model.apply(p, t)[0],
        in_shardings=(None, NamedSharding(mesh, P("data", None))),
    )(params, toks)
err = float(jnp.max(jnp.abs(plain - sharded)))
assert err < 1e-4, err
print("context-parallel parity ok", err)
"""


@pytest.mark.slow
def test_context_parallel_matches_unsharded():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "parity ok" in out.stdout


def test_seq_shard_noop_without_mesh(key):
    """attn_seq_shard archs run unchanged on a plain single device."""
    for arch in ("yi-34b", "whisper-large-v3", "llama4-maverick-400b-a17b"):
        cfg = get_config(arch)
        assert cfg.attn_seq_shard
        r = cfg.reduced()
        model = build_model(r)
        params = model.init(key)
        toks = jnp.zeros((2, 8), jnp.int32)
        extra = None
        if r.stub_frames:
            extra = jnp.zeros((2, r.stub_frames, r.d_model), r.compute_dtype)
        logits, _ = model.apply(params, toks, extra_embeddings=extra)
        assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_fp8_kv_cache_decodes(key):
    cfg = get_config("qwen3-8b").reduced().replace(
        kv_cache_dtype_str="float8_e4m3fn")
    model = build_model(cfg)
    params = model.init(key)
    cache = model.init_cache(2, 16)
    leaf = jax.tree.leaves(cache)[0]
    assert leaf.dtype == jnp.float8_e4m3fn
    tok = jnp.zeros((2, 1), jnp.int32)
    for i in range(4):
        logits, cache = model.decode_step(params, tok, cache,
                                          jnp.asarray(i, jnp.int32))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_fp8_cache_close_to_bf16(key):
    """fp8 cache is a controlled approximation: logits stay close."""
    base = get_config("qwen3-8b").reduced()
    m1 = build_model(base)
    m2 = build_model(base.replace(kv_cache_dtype_str="float8_e4m3fn"))
    params = m1.init(key)
    toks = jax.random.randint(key, (2, 12), 0, base.vocab_size, jnp.int32)
    c1, c2 = m1.init_cache(2, 12), m2.init_cache(2, 12)
    for i in range(12):
        l1, c1 = m1.decode_step(params, toks[:, i:i+1], c1,
                                jnp.asarray(i, jnp.int32))
        l2, c2 = m2.decode_step(params, toks[:, i:i+1], c2,
                                jnp.asarray(i, jnp.int32))
    d = float(jnp.mean(jnp.abs(l1 - l2)))
    scale = float(jnp.mean(jnp.abs(l1))) + 1e-9
    assert d / scale < 0.15, (d, scale)