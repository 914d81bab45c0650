"""Runtime substrate: checkpointing, data pipeline, compression, serving
(1-device)."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data import SyntheticLMData
from repro.optim import compress
from repro.runtime import checkpoint


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.int32)},
            "scalar": jnp.float32(3.5)}
    path = checkpoint.save(str(tmp_path), 7, tree)
    assert os.path.isdir(path)
    like = jax.tree.map(lambda a: jnp.zeros_like(a), tree)
    restored, step = checkpoint.restore(str(tmp_path), 7, like)
    assert step == 7
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), tree, restored)


def test_checkpoint_retention_and_latest(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in range(6):
        checkpoint.save(str(tmp_path), s, tree, keep=3)
    assert checkpoint.latest_step(str(tmp_path)) == 5
    kept = sorted(os.listdir(tmp_path))
    assert len([d for d in kept if d.startswith("ckpt_")]) == 3


def test_checkpoint_crash_safety(tmp_path):
    """A leftover .tmp dir (simulated crash) never corrupts restore."""
    tree = {"x": jnp.arange(4.0)}
    checkpoint.save(str(tmp_path), 1, tree)
    os.makedirs(os.path.join(tmp_path, "ckpt_00000002.tmp"))
    assert checkpoint.latest_step(str(tmp_path)) == 1
    restored, _ = checkpoint.restore(str(tmp_path), 1, tree)
    np.testing.assert_array_equal(np.asarray(restored["x"]),
                                  np.asarray(tree["x"]))


def test_checkpoint_resume_skips_truncated_npz(tmp_path):
    """Regression (DESIGN.md §17 satellite): a partially-written
    `arrays.npz` in the newest checkpoint must not kill the resume —
    `latest_step` warns, skips it, and falls back to the newest intact
    step, and `restore` of that step round-trips."""
    tree = {"x": jnp.arange(4.0), "n": {"y": jnp.ones((3,), jnp.int32)}}
    checkpoint.save(str(tmp_path), 1, tree)
    checkpoint.save(str(tmp_path), 2, tree)
    npz = os.path.join(tmp_path, "ckpt_00000002", "arrays.npz")
    with open(npz, "rb") as f:
        blob = f.read()
    with open(npz, "wb") as f:
        f.write(blob[: len(blob) // 2])   # torn write
    with pytest.warns(UserWarning, match="skipping unreadable checkpoint"):
        step = checkpoint.latest_step(str(tmp_path))
    assert step == 1
    restored, got = checkpoint.restore(str(tmp_path), step, tree)
    assert got == 1
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, restored)


def test_checkpoint_resume_skips_corrupt_manifest(tmp_path):
    """Same fallback for a corrupt/incomplete manifest.json; with NO intact
    checkpoint left, latest_step reports None (fresh start) instead of
    crashing."""
    tree = {"x": jnp.arange(4.0)}
    checkpoint.save(str(tmp_path), 1, tree)
    checkpoint.save(str(tmp_path), 2, tree)
    with open(os.path.join(tmp_path, "ckpt_00000002",
                           "manifest.json"), "w") as f:
        f.write('{"step": 2, "keys"')   # truncated JSON
    with pytest.warns(UserWarning, match="ckpt_00000002"):
        assert checkpoint.latest_step(str(tmp_path)) == 1
    os.remove(os.path.join(tmp_path, "ckpt_00000001", "manifest.json"))
    with pytest.warns(UserWarning):
        assert checkpoint.latest_step(str(tmp_path)) is None


def test_data_pipeline_determinism_and_sharding():
    data = SyntheticLMData(vocab_size=1000, seq_len=64, global_batch=8, seed=3)
    b1 = data.batch(5)
    b2 = data.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (8, 64)
    assert b1["tokens"].max() < 1000
    # shards partition the global batch deterministically
    shards = [data.batch(5, shard=i, n_shards=4)["tokens"] for i in range(4)]
    assert all(s.shape == (2, 64) for s in shards)
    # different steps differ
    assert not np.array_equal(b1["tokens"], data.batch(6)["tokens"])


def test_data_has_learnable_structure():
    """Bigram continuation rate is far above uniform chance."""
    data = SyntheticLMData(vocab_size=500, seq_len=256, global_batch=4, seed=0)
    toks = data.batch(0)["tokens"]
    succ = data._succ
    hits = 0
    total = 0
    for b in range(toks.shape[0]):
        for t in range(1, toks.shape[1]):
            hits += toks[b, t] in succ[toks[b, t - 1]]
            total += 1
    assert hits / total > 0.5


@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import get_config, reduced_config
    from repro.models import transformer
    cfg = reduced_config(get_config("llama3.2-3b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    return cfg, params, {"tokens": tok}


def test_generate_zero_tokens_returns_empty(serve_setup):
    """Regression: n_tokens=0 used to return 1 token (the prefill argmax)."""
    from repro.runtime import lm_serve as serve
    cfg, params, batch = serve_setup
    out = serve.generate(params, cfg, batch, n_tokens=0, s_max=32)
    assert out.shape == (2, 0)


def test_generate_sampling_is_wired(serve_setup):
    """Regression: greedy/key used to be accepted but silently ignored —
    sampling degraded to argmax. Now: greedy ignores the key, sampling is
    key-deterministic, key-sensitive, and collapses to greedy as T -> 0."""
    from repro.runtime import lm_serve as serve
    cfg, params, batch = serve_setup
    greedy = serve.generate(params, cfg, batch, n_tokens=5, s_max=32)
    greedy_keyed = serve.generate(params, cfg, batch, n_tokens=5, s_max=32,
                                  key=jax.random.PRNGKey(7))
    assert greedy.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(greedy_keyed))

    sample = lambda k, t: serve.generate(
        params, cfg, batch, n_tokens=5, s_max=32, greedy=False,
        key=jax.random.PRNGKey(k), temperature=t)
    s1, s2 = sample(3, 2.0), sample(3, 2.0)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert 0 <= int(s1.min()) and int(s1.max()) < cfg.vocab_size
    # different keys must be able to produce different sequences
    assert any(not np.array_equal(np.asarray(s1), np.asarray(sample(k, 2.0)))
               for k in (5, 11, 23))
    # near-zero temperature collapses to the greedy sequence
    cold = sample(9, 1e-5)
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(greedy))


def test_generate_sampling_requires_key(serve_setup):
    from repro.runtime import lm_serve as serve
    cfg, params, batch = serve_setup
    with pytest.raises(ValueError, match="key"):
        serve.generate(params, cfg, batch, n_tokens=2, s_max=32, greedy=False)


def test_int8_quantize_roundtrip_error():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(0, 0.1, (256, 128)).astype(np.float32))
    q, s = compress.quantize_int8(g)
    back = compress.dequantize_int8(q, s)
    # error bounded by half a quantization step
    assert float(jnp.max(jnp.abs(back - g))) <= float(s) * 0.5 + 1e-9
    assert q.dtype == jnp.int8


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_config(monkeypatch):
    """No cache directory from the environment (conftest.py restores jax's
    cache options after the test)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


def test_compile_cache_env_var_wins(cache_config, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR is used as is: configure returns it, sets no
    directory of its own, and the command-line directory is ignored."""
    from repro.runtime import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    got = compile_cache.configure(str(tmp_path / "flag"))
    assert got == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "flag").exists()


def test_compile_cache_default_is_fixed_inside_checkout(cache_config):
    """Without the variable or a flag the cache goes to one fixed directory
    inside the checkout, the same on every call."""
    from pathlib import Path

    from repro.runtime import compile_cache

    first = compile_cache.configure()
    second = compile_cache.configure()
    checkout = Path(__file__).resolve().parents[1]
    assert first == second == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert jax.config.jax_enable_compilation_cache


def test_compile_cache_flag_used_without_env_var(cache_config, tmp_path):
    from repro.runtime import compile_cache

    got = compile_cache.configure(str(tmp_path / "flag"))
    assert got == str(tmp_path / "flag")
    assert jax.config.jax_compilation_cache_dir == got
    assert (tmp_path / "flag").is_dir()


def test_compile_cache_written_to_env_dir(tmp_path):
    """End to end, in a fresh interpreter: with JAX_COMPILATION_CACHE_DIR set
    the compiled program lands in that directory."""
    import subprocess
    import sys
    from pathlib import Path

    env_dir = tmp_path / "env"
    src = Path(__file__).resolve().parents[1] / "src"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.runtime import compile_cache\n"
            "print(compile_cache.configure())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(7)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(env_dir),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(src))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [str(env_dir), str(env_dir)]
    assert any(env_dir.iterdir())
