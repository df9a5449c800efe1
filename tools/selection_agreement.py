#!/usr/bin/env python3
"""How many of a position's selected rows differ between the server's
arithmetic and the reference's, at GLM-5's published widths: the boundary of
a top-k is where bfloat16 shows.

    JAX_PLATFORMS=cpu python tools/selection_agreement.py [--tokens 4096]
        [--seed 1]

Layer 0 alone (its selection depends on the embedding and the indexer's
projections only): the system's ``models.joyai.index_inputs`` in bfloat16 —
activations, the cached index key, the products' operands — scored by
``ops.attention.index_scores`` and selected by ``select_rows``, against
``benchmark/reference/glm5.py``'s float32 ``index_scores`` and ``lax.top_k``
over the same stored weights.  Prints one JSON row: over the positions past
``index_topk``, the mean and the largest number of the ``index_topk`` selected
rows that are not in the reference's set.  Runs on the CPU (the arithmetic's
rounding, not the chip's speed, is the question).
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="glm5_ep16")
    p.add_argument("--tokens", type=int, default=4096)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import joyai
    from distributedtensorflow_tpu.models.afmoe import rms_norm
    from distributedtensorflow_tpu.ops import attention

    spec = importlib.util.spec_from_file_location(
        "ref_glm5", os.path.join(ROOT, "benchmark", "reference", "glm5.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    # one layer deep: the draws of wte and h0 are the whole model's
    cfg = dataclasses.replace(getattr(models, args.config)(), num_layers=1,
                              num_dense_layers=1)
    params = joyai.init_params(cfg, jax.random.PRNGKey(args.seed))
    s, k = args.tokens, cfg.index_topk
    ids = np.random.default_rng(args.seed).integers(0, cfg.vocab_size, s)
    a = params["h0"]["attn"]
    positions = jnp.arange(s, dtype=jnp.int32)

    x = params["wte"][ids]
    h = rms_norm(x, params["h0"]["ln_attn"], cfg.rms_norm_eps)
    *_, c_q = joyai.latent_inputs(a, h, cfg, positions)
    q, w, key = joyai.index_inputs(a["indexer"], h, c_q, cfg, positions)
    scores = attention.index_scores(q[None], w[None], key[None],
                                    jnp.asarray([s]), impl="xla")[0]
    served, _ = attention.select_rows(scores, positions + 1, k)

    config = {"index_n_heads": cfg.index_heads,
              "index_head_dim": cfg.index_head_dim,
              "qk_rope_head_dim": cfg.qk_rope_head_dim,
              "rope_parameters": {"rope_theta": cfg.rope_theta}}
    with jax.default_matmul_precision("highest"):
        xf = jnp.asarray(x, jnp.float32)
        hf = ref._rms_norm(xf, params["h0"]["ln_attn"], cfg.rms_norm_eps)
        cf = ref._rms_norm(hf @ ref._f32(a["w_qa"]), a["q_norm"],
                           cfg.rms_norm_eps)
        want_scores = ref.index_scores(a["indexer"], hf, cf, config)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    _, want = jax.lax.top_k(jnp.where(causal, want_scores, -jnp.inf), k)

    served, want = np.asarray(served), np.asarray(want)
    differ = [k - len(np.intersect1d(served[t], want[t]))
              for t in range(k, s)]
    print(json.dumps({
        "config": args.config, "layer": 0, "tokens": s, "index_topk": k,
        "positions": len(differ),
        "rows_differing_mean": round(float(np.mean(differ)), 2),
        "rows_differing_max": int(np.max(differ)),
        "share_of_topk_pct": round(100 * float(np.mean(differ)) / k, 3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
