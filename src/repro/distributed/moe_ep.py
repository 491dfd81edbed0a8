"""Expert-parallel MoE dispatch with explicit all-to-all (shard_map).

The optimized counterpart to the GSPMD scatter/gather baseline in
repro.models.moe: a deterministic collective schedule,

    local top-k -> capacity buffer (E, C, d)
      -> all_to_all over 'model'   (tokens travel to their experts)
      -> per-local-expert SwiGLU   (E_loc, M*C, d)
      -> reverse all_to_all        (results travel home)
      -> weighted combine

This is FLIP's data-centric mode verbatim: data (tokens) routed to
statically-placed compute sites (experts), with the placement compiled by
repro.core.placement to cut traffic. Falls back to the GSPMD path when
num_experts doesn't divide the model-axis size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh


def _capacity(tokens: int, num_experts: int, k: int, factor: float) -> int:
    c = int(np.ceil(tokens * k * factor / num_experts))
    return max(8, -(-c // 8) * 8)


def moe_all_to_all(p, x, cfg, model_axis: str = "model"):
    """x: (B, S, d) with batch over DP axes and seq over `model_axis`.

    Returns (y (B,S,d), aux loss). Requires E % mesh[model_axis] == 0.
    """
    mesh = current_mesh()
    m = mesh.shape[model_axis]
    e, k = cfg.num_experts, cfg.top_k
    assert e % m == 0
    e_loc = e // m
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    def local_fn(wr, wg, wi, wo, x_loc):
        # wr: (d, e) replicated; wg/wi: (e_loc, d, f); wo: (e_loc, f, d)
        b_loc, s_loc, d = x_loc.shape
        t_loc = b_loc * s_loc
        xt = x_loc.reshape(t_loc, d)
        logits = jnp.einsum("td,de->te", xt, wr).astype(jnp.float32)
        vals, ids = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(vals, axis=-1)

        # aux (switch-style) with psums across the whole mesh
        probs = jax.nn.softmax(logits, axis=-1)
        occ = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0)
        axes = dp + (model_axis,)
        occ = jax.lax.psum(occ, axes)
        pm = jax.lax.psum(probs.sum(axis=0), axes)
        n_tok = jax.lax.psum(jnp.float32(t_loc), axes)
        aux = jnp.sum((occ / (n_tok * k)) * (pm / n_tok)) * e

        # capacity dispatch (local scatter into (E, C, d))
        cap = _capacity(t_loc, e, k, cfg.capacity_factor)
        flat = ids.reshape(-1)
        onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1)
        pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
        keep = pos < cap
        src = jnp.repeat(jnp.arange(t_loc), k)
        buf = jnp.zeros((e, cap, d), x_loc.dtype)
        buf = buf.at[flat, jnp.where(keep, pos, 0)].add(
            jnp.where(keep[:, None], xt[src], 0.0), mode="drop")

        # tokens -> expert shards
        recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
        # recv: (e_loc, M*C, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg)) \
            * jnp.einsum("ecd,edf->ecf", recv, wi)
        out = jnp.einsum("ecf,efd->ecd", h, wo)
        # results -> home shards
        back = jax.lax.all_to_all(out, model_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        # back: (e, C, d)
        gathered = back[flat, jnp.where(keep, pos, 0)]
        gathered = jnp.where(keep[:, None], gathered,
                             jnp.zeros((), out.dtype))
        y = jnp.sum(gathered.reshape(t_loc, k, d)
                    * weights.astype(out.dtype)[:, :, None], axis=1)
        return y.reshape(b_loc, s_loc, d), aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None),                     # router replicated
                  P(model_axis, None, None),         # experts sharded
                  P(model_axis, None, None),
                  P(model_axis, None, None),
                  P(dp_spec, model_axis, None)),     # x: batch x seq-shard
        out_specs=(P(dp_spec, model_axis, None), P()),
        check_vma=False)
    return fn(p["router"], p["w_gate"], p["w_in"], p["w_out"], x)
