"""Mixture-of-Experts block (olmoe 64e top-8; qwen2-moe 60e top-4 + shared).

Dispatch design (TPU-native, recorded in DESIGN.md):
  * top-k routing with softmax gates, normalised over the selected experts;
  * capacity-based dispatch (GShard/Switch style): tokens are sorted by
    expert id *locally per data shard* and gathered into a dense
    ``[E, C, D]`` block, so the expert computation is one batched MXU einsum
    — no [T, E, C] one-hot dispatch tensor, no ragged ops;
  * expert weights are **tensor-parallel over the ff dim** (each model-axis
    shard holds F/model columns of every expert).  That keeps the MoE layer's
    collective cost identical to a dense MLP (one reduce over `model`) and
    avoids the all-to-all of expert-parallel placement — the trade-off is
    analysed in EXPERIMENTS.md §Perf.  Tokens over capacity are dropped
    (standard dropping-MoE semantics; capacity_factor configures slack).

The local math (`moe_local`) is pure and shard-free; `moe_apply` wraps it in
shard_map when a mesh is given so the sort/gather stay device-local.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.params import ParamInfo
from repro.utils.config import ModelConfig


def moe_infos(cfg: ModelConfig) -> Dict[str, ParamInfo]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    infos = {
        "router": ParamInfo((d, e), ("embed", "experts"), dtype=jnp.float32),
        "w_gate": ParamInfo((e, d, f), ("experts", "embed", "ff")),
        "w_up": ParamInfo((e, d, f), ("experts", "embed", "ff")),
        "w_down": ParamInfo((e, f, d), ("experts", "ff", "embed")),
    }
    if cfg.num_shared_experts:
        fs = cfg.shared_expert_d_ff
        infos.update({
            "s_gate": ParamInfo((d, fs), ("embed", "ff")),
            "s_up": ParamInfo((d, fs), ("embed", "ff")),
            "s_down": ParamInfo((fs, d), ("ff", "embed")),
        })
    return infos


def _capacity(tokens: int, k: int, e: int, cf: float) -> int:
    return int(min(tokens, max(math.ceil(tokens * k / e * cf), 8)))


def moe_local(p, x: jnp.ndarray, cfg: ModelConfig,
              capacity_factor: float = 1.25) -> jnp.ndarray:
    """Routed experts over local tokens.  x: [T, D] → [T, D] (partial over
    the ff shard when weights are column-sharded; caller reduces)."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    c = _capacity(t, k, e, capacity_factor)

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                       # [T, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # sort the (token, expert) pairs by expert id; position within an expert
    # group = slot; beyond capacity → dropped (scatter mode='drop').
    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1).astype(x.dtype)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    start = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    pos = jnp.arange(t * k, dtype=jnp.int32) - start[se].astype(jnp.int32)
    keep = pos < c
    se_s = jnp.where(keep, se, e)                                # OOB → drop

    slot_tok = jnp.full((e, c), t, dtype=jnp.int32)              # t = pad row
    slot_tok = slot_tok.at[se_s, pos].set(st, mode="drop")
    slot_w = jnp.zeros((e, c), x.dtype).at[se_s, pos].set(sw, mode="drop")

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = x_pad[slot_tok]                                         # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"])
    y = y * slot_w[..., None]

    out = jnp.zeros((t + 1, d), y.dtype)
    out = out.at[slot_tok.reshape(-1)].add(y.reshape(-1, d))[:t]

    if cfg.num_shared_experts:
        g = jnp.einsum("td,df->tf", x, p["s_gate"])
        uu = jnp.einsum("td,df->tf", x, p["s_up"])
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(g) * uu, p["s_down"])
    return out.astype(x.dtype)


def moe_apply(p, x: jnp.ndarray, cfg: ModelConfig, *,
              mesh=None, batch_axes=("data",), model_axis: str = "model",
              capacity_factor: float = 1.25) -> jnp.ndarray:
    """MoE over x: [B, S, D].  With a mesh: shard_map so the per-shard sort
    and gather never cross devices; ff-sharded experts psum over `model`."""
    b, s, d = x.shape
    if mesh is None:
        return moe_local(p, x.reshape(-1, d), cfg,
                         capacity_factor).reshape(b, s, d)

    from jax.sharding import PartitionSpec as PS

    def local_fn(p_l, x_l):
        bl, sl, _ = x_l.shape
        y = moe_local(p_l, x_l.reshape(-1, d), cfg, capacity_factor)
        y = jax.lax.psum(y, model_axis)
        return y.reshape(bl, sl, d)

    p_specs = {
        "router": PS(),                               # replicated (fp32)
        "w_gate": PS(None, None, model_axis),
        "w_up": PS(None, None, model_axis),
        "w_down": PS(None, model_axis, None),
    }
    if cfg.num_shared_experts:
        p_specs.update({"s_gate": PS(None, model_axis),
                        "s_up": PS(None, model_axis),
                        "s_down": PS(model_axis, None)})
    x_spec = PS(batch_axes, None, None)
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(p_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn(p, x)
