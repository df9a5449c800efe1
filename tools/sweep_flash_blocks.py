"""On-chip flash-attention block-size sweep at short sequence lengths.

Round-4 profile evidence (BENCH_RESULTS/profile_lm_tpu, 2026-08-01): XLA
dense attention costs 105 ms of the 214 ms GPT-2-small step (seq 1024,
bs 16) running HBM-bound at ~740 GB/s, while its FLOPs floor is ~13 ms.
The flash kernel SHOULD win there but measured ~132 ms/bs16-equivalent
end-to-end (lm_bs32_pl): suspicion is grid-step overhead — the default
(block_q=128, block_k=512) tiling runs B*H*n_q*n_k = 3072 grid steps per
layer at seq 1024, each doing one tiny (128,64)x(64,512) matmul.

This sweep times the kernel (fwd and fwd+bwd) across block tilings via
the DTFT_FLASH_BLOCK_Q/K env overrides, against the XLA dense reference,
at the headline LM shapes.  Run on the real chip:

    python tools/sweep_flash_blocks.py            # B=16 H=12 S=1024 D=64
    SWEEP_SEQ=2048 SWEEP_BATCH=8 python tools/sweep_flash_blocks.py

Timing discipline: every measurement chains the op k times (output feeds
the next iteration's query) and waits for the last output, so dispatch
cost amortizes over the chain.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timed(fn, args, iters=12):
    """Compile, warm, then time ``iters`` chained calls; returns ms/call."""
    out = fn(*args)                      # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    x = args[0]
    for _ in range(iters):
        out = fn(x, *args[1:])
        x = out[0] if isinstance(out, tuple) else out
    jax.block_until_ready(x)
    return 1e3 * (time.perf_counter() - t0) / iters


def main():
    from bench_common import start

    start("sweep_flash_blocks")
    b = int(os.environ.get("SWEEP_BATCH", 16))
    h = int(os.environ.get("SWEEP_HEADS", 12))
    s = int(os.environ.get("SWEEP_SEQ", 1024))
    d = int(os.environ.get("SWEEP_DEPTH", 64))
    iters = int(os.environ.get("SWEEP_ITERS", 12))
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (b, s, h, d),
                          jnp.bfloat16)
        for i in range(3)
    )

    from distributedtensorflow_tpu.ops import flash_attention as fa
    from distributedtensorflow_tpu.ops.attention import xla_attention

    rows = []

    def add(name, fwd_ms, bwd_ms):
        rows.append({"config": name, "fwd_ms": round(fwd_ms, 2),
                     "fwdbwd_ms": round(bwd_ms, 2)})
        print(f"{name:>14}: fwd {fwd_ms:7.2f} ms   fwd+bwd {bwd_ms:7.2f} ms",
              flush=True)

    # Dense XLA reference (what the profile blames).
    try:
        dense = jax.jit(lambda q, k, v: xla_attention(q, k, v, causal=True))
        dense_g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                xla_attention(q, k, v, causal=True).astype(jnp.float32) ** 2
            ), argnums=(0, 1, 2)))
        add("xla_dense", timed(dense, (q, k, v), iters),
            timed(dense_g, (q, k, v), iters))
    except Exception as e:
        print(f"xla_dense: FAILED ({str(e)[:120]})", flush=True)

    combos = os.environ.get(
        "SWEEP_BLOCKS",
        "128:512,256:512,512:512,256:256,256:1024,512:1024,1024:1024",
    )
    for combo in combos.split(","):
        bq, bk = (int(x) for x in combo.split(":"))
        if s % bq or s % bk:
            continue
        os.environ["DTFT_FLASH_BLOCK_Q"] = str(bq)
        os.environ["DTFT_FLASH_BLOCK_K"] = str(bk)
        try:
            # Fresh function objects per combo: the env override is read at
            # TRACE time, so reusing one jitted callable would silently
            # reuse the first tiling.
            fwd = jax.jit(
                lambda q, k, v, _bq=bq: fa.flash_attention(q, k, v,
                                                           causal=True))
            grd = jax.jit(jax.grad(
                lambda q, k, v, _bq=bq: jnp.sum(
                    fa.flash_attention(q, k, v, causal=True)
                    .astype(jnp.float32) ** 2
                ), argnums=(0, 1, 2)))
            add(f"flash_{bq}x{bk}", timed(fwd, (q, k, v), iters),
                timed(grd, (q, k, v), iters))
        except Exception as e:
            print(f"flash_{bq}x{bk}: FAILED ({str(e)[:160]})", flush=True)
        finally:
            os.environ.pop("DTFT_FLASH_BLOCK_Q", None)
            os.environ.pop("DTFT_FLASH_BLOCK_K", None)

    out = {
        "metric": "flash_block_sweep",
        "shape": {"batch": b, "heads": h, "seq": s, "depth": d},
        "device_kind": jax.devices()[0].device_kind,
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if os.environ.get("SWEEP_PERSIST", "1") == "1":
        from bench_common import persist_result

        persist_result("flashsweep", out)


if __name__ == "__main__":
    main()
