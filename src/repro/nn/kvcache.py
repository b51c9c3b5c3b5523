"""Decode-time caches.

KVCache: (B, S_max, n_kv, d_head) k/v ring buffers + scalar write position.
SSMCache: Mamba2 recurrent state (B, H, d_state, d_headdim) + conv tail.

Caches are plain pytrees so they thread through jit/scan and shard via the
logical rules ("kv_seq" binds to the data axis for long-context SP decode).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import logical

Array = jnp.ndarray


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    k: Array      # (B, S_max, n_kv, d_head)
    v: Array
    pos: Array    # scalar int32 — next write index (same for all rows)

    def tree_flatten(self):
        return (self.k, self.v, self.pos), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    @classmethod
    def zeros(cls, batch: int, s_max: int, n_kv: int, d_head: int, dtype=jnp.bfloat16):
        shape = (batch, s_max, n_kv, d_head)
        return cls(
            k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
            pos=jnp.zeros((), jnp.int32),
        )

    def shard(self) -> "KVCache":
        names = ("batch", "kv_seq", "kv_heads", "head_dim")
        return KVCache(logical(self.k, *names), logical(self.v, *names), self.pos)

    def update(self, k_new: Array, v_new: Array) -> "KVCache":
        """Append S_new timesteps (B, S_new, n_kv, d_head) at ``pos``.

        Sharding-aware write paths: a
        dynamic_update_slice into a cache whose sequence (or head) dim is
        sharded makes GSPMD all-gather the WHOLE cache every decode step
        (measured: 11.5 GB/chip/step on zamba2 long_500k).  So:
          * S_new == S_max  (prefill from zero): replace outright — no DUS.
          * S_new == 1      (decode): one-hot masked merge — elementwise,
            partitions cleanly on every dim; costs one cache re-write,
            which is the same order as the attention read it feeds.
          * otherwise (chunked prefill): DUS fallback.
        """
        kd, vd = k_new.astype(self.k.dtype), v_new.astype(self.v.dtype)
        s_new, s_max = k_new.shape[1], self.k.shape[1]
        if s_new == s_max:
            k, v = kd, vd
        elif s_new == 1:
            oh = (jnp.arange(s_max, dtype=jnp.int32) == self.pos)
            oh = oh.astype(self.k.dtype)[None, :, None, None]
            k = self.k * (1 - oh) + kd * oh
            v = self.v * (1 - oh) + vd * oh
        else:
            k = jax.lax.dynamic_update_slice(self.k, kd, (0, self.pos, 0, 0))
            v = jax.lax.dynamic_update_slice(self.v, vd, (0, self.pos, 0, 0))
        return KVCache(k, v, self.pos + s_new).shard()

    def valid_mask(self, s_max: Optional[int] = None) -> Array:
        """(S_max,) bool — which cache slots hold live tokens."""
        s_max = s_max or self.k.shape[1]
        return jnp.arange(s_max, dtype=jnp.int32) < self.pos


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SSMCache:
    state: Array      # (B, H, d_state, headdim)
    conv: Array       # (B, conv_width - 1, conv_channels)

    def tree_flatten(self):
        return (self.state, self.conv), None

    @classmethod
    def tree_unflatten(cls, _aux, leaves):
        return cls(*leaves)

    @classmethod
    def zeros(cls, batch: int, n_heads: int, d_state: int, headdim: int,
              conv_width: int, conv_channels: int, dtype=jnp.float32):
        return cls(
            state=jnp.zeros((batch, n_heads, d_state, headdim), dtype),
            conv=jnp.zeros((batch, conv_width - 1, conv_channels), dtype),
        )

    def shard(self) -> "SSMCache":
        return SSMCache(
            logical(self.state, "batch", "ssm_heads", "ssm_state", None),
            logical(self.conv, "batch", None, "d_ff"),
        )
