"""Decode caches: ring-buffered KV for attention, recurrent state for SSM.

Per-layer cache length is *pattern-aware* — the production memory story for
the long-context archs:

  * full-attention layers  → max_seq slots
  * sliding-window layers  → `window` slots (ring buffer; stale slots are
    masked by their stored absolute positions, so no shifting ever happens)
  * chunked layers         → `window` (= chunk) slots, same ring mechanics
  * ssm layers             → O(1): (B, H, N, P) state + 3-step conv tail

At jamba's long_500k cell this is the difference between 9 attention layers
holding 500k KV (19 GB total) and 72 layers doing so (155 GB).

Cache k/v length is sharded over the model axis (flash-decoding style):
every arch divides 16 evenly in the seq dim, unlike kv-heads (8 < 16), and
attention over a seq-sharded cache partitions into per-shard partial
softmaxes combined by the SPMD partitioner.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.blocks import split_layers
from repro.models.ssm import init_ssm_cache


def layer_cache_len(cfg, mixer: str, max_seq: int) -> int:
    if mixer == "attn_full":
        return max_seq
    return min(cfg.window or max_seq, max_seq)


def init_layer_cache(cfg, mixer: str, batch: int, max_seq: int):
    if mixer == "ssm":
        return init_ssm_cache(cfg, batch, cfg.dtype)
    length = layer_cache_len(cfg, mixer, max_seq)
    hk, dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, length, hk, dh), cfg.dtype),
        "v": jnp.zeros((batch, length, hk, dh), cfg.dtype),
        "pos": jnp.full((length,), -1, jnp.int32),
        "step": jnp.zeros((), jnp.int32),
    }


def init_caches(cfg, batch: int, max_seq: int) -> dict:
    """Cache tree mirroring the param stack ({"periods": stacked, ...})."""
    n_periods, rem = split_layers(cfg)

    def one_period():
        return {
            f"l{i}": init_layer_cache(cfg, mixer, batch, max_seq)
            for i, (mixer, _) in enumerate(cfg.pattern)
        }

    periods = [one_period() for _ in range(n_periods)]
    out = {"periods": jax.tree.map(lambda *xs: jnp.stack(xs), *periods)}
    if rem:
        out["remainder"] = {
            f"l{i}": init_layer_cache(cfg, cfg.pattern[i][0], batch, max_seq)
            for i in range(rem)
        }
    return out


def cache_logical_specs(cfg, cache_tree) -> dict:
    """Logical PartitionSpec names per cache leaf (resolved by rules)."""

    def spec_for(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        leading = ("periods" in [str(getattr(p, "key", "")) for p in path])
        base: tuple
        last = names[-1] if names else ""
        if last in ("k", "v"):
            base = ("batch", "model", None, None)
        elif last == "pos":
            base = ("model",)
        elif last == "step":
            base = ()
        elif last == "state":
            base = ("batch", "model", None, None)
        elif last == "conv":
            base = ("batch", None, None)
        else:
            base = tuple(None for _ in leaf.shape)
        if leading and len(base) < len(leaf.shape):
            base = (None,) + base
        return base

    return jax.tree.map_with_path(spec_for, cache_tree)


def merge_cache_updates(old: dict, upd: dict) -> dict:
    """Fold per-layer decode deltas into the cache tree.

    Attention layers emit {k_new, v_new, pos_new} (see models/attention.py —
    the write is deferred out of the period scan so XLA cannot materialize
    f32 copies of the stacked buffers); SSM layers emit full replacement
    states. Stacked (per-period) and unstacked (remainder) layers both
    supported; the ring index comes from the layer's own step counter.
    """
    import jax.numpy as jnp
    from jax import lax

    def merge_layer(o: dict, u: dict) -> dict:
        if "state" in u:  # ssm: full replacement
            return u
        cl = o["k"].shape[-3]
        step0 = o["step"].reshape(-1)[0]
        idx = (step0 % cl).astype(jnp.int32)
        z = jnp.zeros((), jnp.int32)
        if o["k"].ndim == 5:  # stacked over periods
            starts4 = (z, z, idx, z, z)
            pstarts = (z, idx)
        else:
            starts4 = (z, idx, z, z)
            pstarts = (idx,)
        # pos_new arrives as (1,) unstacked or (P, 1) stacked — exactly the
        # update-slice shape for pos of (L,) / (P, L)
        return {
            "k": lax.dynamic_update_slice(o["k"], u["k_new"], starts4),
            "v": lax.dynamic_update_slice(o["v"], u["v_new"], starts4),
            "pos": lax.dynamic_update_slice(o["pos"], u["pos_new"], pstarts),
            "step": o["step"] + 1,
        }

    out = {}
    for section in old:
        out[section] = {
            name: merge_layer(old[section][name], upd[section][name])
            for name in old[section]
        }
    return out


def cache_bytes(cfg, batch: int, max_seq: int) -> int:
    tree = jax.eval_shape(lambda: init_caches(cfg, batch, max_seq))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
