"""What the tests of ``flash_attention_qkv`` share: a fused projection with
its positions and lane tables, and its split into heads
(``test_flash_attention_qkv.py``, ``test_flash_attention_tiles.py``)."""

import jax
import jax.numpy as jnp


def fused_case(d, h, *, s=128, b=2, per_row=False, seed=0):
    """A projection (B, S, 3*H*D), its positions, and the rotation's lane
    tables as the trunk hands them to the blocks."""
    from distributedtensorflow_tpu.models.gpt import rope_lane_tables

    qkv = jax.random.normal(jax.random.PRNGKey(seed + d), (b, s, 3 * h * d))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    if per_row:
        pos = pos + 7 * jnp.arange(b)[:, None]
    return qkv, pos, rope_lane_tables(pos if per_row else pos[:1], d, 1e4)


def split_heads(qkv, h):
    b, s, w = qkv.shape
    return tuple(x.reshape(b, s, h, w // (3 * h))
                 for x in jnp.split(qkv, 3, axis=-1))
