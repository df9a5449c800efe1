"""ops/flash_tuning.py + the flash-attention block resolver: cache
write/read/invalidate roundtrip, resolution precedence, kernel
correctness at cache-picked tilings, the autotune CLI, and the schema
gate (PR 8 tentpole)."""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import pytest

from distributedtensorflow_tpu.ops import flash_tuning
from distributedtensorflow_tpu.ops.attention import xla_attention
from distributedtensorflow_tpu.ops.flash_attention import (
    _resolve_blocks,
    flash_attention,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B, H, S, D = 2, 4, 128, 32


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    path = str(tmp_path / "flash_blocks.json")
    monkeypatch.setenv("DTFT_FLASH_TUNE_CACHE", path)
    yield path


def _entry(**kw):
    e = {"platform": jax.default_backend(), "dtype": "float32",
         "batch": B, "heads": H, "seq": S, "depth": D,
         "block_q": 32, "block_k": 64, "ms": 1.5}
    e.update(kw)
    return e


class TestCacheRoundtrip:
    def test_store_lookup_invalidate(self, cache):
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D) is None
        flash_tuning.store(_entry())
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D, batch=B, heads=H) == (32, 64)
        # replace: same key, newer measurement wins
        flash_tuning.store(_entry(block_q=64, block_k=64, ms=1.0))
        doc = json.load(open(cache))
        assert len(doc["entries"]) == 1
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D) == (64, 64)
        flash_tuning.clear()
        assert not os.path.exists(cache)
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D) is None

    def test_exact_batch_heads_match_preferred(self, cache):
        flash_tuning.store(_entry(batch=99, heads=99, block_q=16,
                                  block_k=16))
        flash_tuning.store(_entry(block_q=32, block_k=32))
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D, batch=B, heads=H) == (32, 32)
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D, batch=99, heads=99) == (16, 16)

    def test_non_dividing_entry_never_consulted(self, cache):
        with pytest.raises(ValueError, match="divide"):
            flash_tuning.store(_entry(block_q=48))
        # a hand-mangled cache file is skipped, not fatal
        with open(cache, "w") as f:
            json.dump({"version": 1, "entries": [_entry(block_q=48)]}, f)
        assert flash_tuning.lookup(
            platform=jax.default_backend(), dtype="float32",
            seq=S, depth=D) is None

    def test_an_entry_is_of_one_kernel_form(self, cache):
        """A tiling recorded for the (B, H, S, D) kernels (every entry
        written before there were two forms: no ``layout``) is no prior for
        the kernels that read the fused projection, nor the other way
        round; each form's entry replaces only its own."""
        where = dict(platform=jax.default_backend(), dtype="float32",
                     seq=S, depth=D, batch=B, heads=H)
        flash_tuning.store(_entry())
        assert flash_tuning.lookup(**where) == (32, 64)
        assert flash_tuning.lookup(**where, layout="qkv_tiles") is None
        assert _resolve_blocks(B, H, S, D, jnp.float32, None, None,
                               layout="qkv_tiles") == (S, S)
        flash_tuning.store(_entry(layout="qkv_tiles", block_q=64,
                                  block_k=32))
        assert len(json.load(open(cache))["entries"]) == 2
        assert flash_tuning.lookup(**where) == (32, 64)
        assert flash_tuning.lookup(**where, layout="qkv_tiles") == (64, 32)
        assert _resolve_blocks(B, H, S, D, jnp.float32, None, None,
                               layout="qkv_tiles") == (64, 32)
        assert _resolve_blocks(B, H, S, D, jnp.float32, None, None) == (
            32, 64)

    def test_corrupt_file_degrades_to_none(self, cache):
        with open(cache, "w") as f:
            f.write("{not json")
        assert flash_tuning.load() == {}

    def test_off_disables(self, monkeypatch):
        monkeypatch.setenv("DTFT_FLASH_TUNE_CACHE", "off")
        assert flash_tuning.cache_path() is None
        assert flash_tuning.load() == {}
        with pytest.raises(ValueError, match="disabled"):
            flash_tuning.store(_entry())

    def test_validate_doc(self, cache):
        flash_tuning.store(_entry())
        assert flash_tuning.validate_doc(json.load(open(cache))) == []
        bad = {"version": 2, "entries": [
            {"platform": "", "dtype": "float32", "seq": 128, "depth": 32,
             "block_q": 48, "block_k": 64, "source": "guess", "ms": -1},
        ]}
        errs = flash_tuning.validate_doc(bad)
        assert any("version" in e for e in errs)
        assert any("divide" in e for e in errs)
        assert any("source" in e for e in errs)
        assert any("ms" in e for e in errs)


class TestResolver:
    def test_precedence_explicit_env_cache_default(self, cache,
                                                   monkeypatch):
        # default chain
        assert _resolve_blocks(B, H, S, D, jnp.float32, None, None) \
            == (128, 128)
        # cache beats default
        flash_tuning.store(_entry(block_q=32, block_k=32))
        assert _resolve_blocks(B, H, S, D, jnp.float32, None, None) \
            == (32, 32)
        # env beats cache
        monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "64")
        assert _resolve_blocks(B, H, S, D, jnp.float32, None, None) \
            == (64, 32)
        # explicit beats everything
        assert _resolve_blocks(B, H, S, D, jnp.float32, 16, 16) == (16, 16)

    def test_non_dividing_env_warns_and_falls_through(self, cache,
                                                      monkeypatch):
        monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "48")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            bq, _ = _resolve_blocks(B, H, S, D, jnp.float32, None, None)
        assert bq == 128
        assert any("does not divide" in str(x.message) for x in w)

    def test_kernel_correct_at_cached_tiling(self, cache):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                   for kk in ks)
        ref = xla_attention(q, k, v, causal=True)
        flash_tuning.store(_entry(block_q=32, block_k=32))
        out = flash_attention(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
        # gradient path resolves the same tiling without error
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True) ** 2
        ))(q)
        assert g.shape == q.shape

    def test_explicit_blocks_validated(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                   for kk in ks)
        with pytest.raises(ValueError, match="block_q"):
            flash_attention(q, k, v, causal=True, block_q=48)


class TestAutotuneCLI:
    def test_sweep_writes_consultable_cache(self, tmp_path):
        cache = str(tmp_path / "flash_blocks.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "autotune_flash.py"),
             "--shape", f"{B},{H},{S},{D}", "--dtype", "float32",
             "--blocks", "64,128", "--steps", "1", "--cache", cache],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["metric"] == "flash_block_autotune"
        assert line["source"] == "sweep"
        doc = json.load(open(cache))
        assert flash_tuning.validate_doc(doc) == []
        assert flash_tuning.lookup(
            platform="cpu", dtype="float32", seq=S, depth=D,
            batch=B, heads=H, path=cache,
        ) == (line["block_q"], line["block_k"])

    def test_schema_checker_gates_cache(self, tmp_path):
        good = tmp_path / "flash_blocks.json"
        with open(good, "w") as f:
            json.dump({"version": 1, "entries": [_entry()]}, f)
        bad = tmp_path / "flash_blocks_bad.json"
        with open(bad, "w") as f:
            json.dump({"version": 1, "entries": [_entry(block_q=48)]}, f)
        tool = os.path.join(REPO, "tools", "check_metrics_schema.py")
        ok = subprocess.run([sys.executable, tool, str(good)],
                            capture_output=True, text=True)
        assert ok.returncode == 0, ok.stdout
        fail = subprocess.run([sys.executable, tool, str(bad)],
                              capture_output=True, text=True)
        assert fail.returncode == 1
        assert "does not divide" in fail.stdout
