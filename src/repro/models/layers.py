"""Core layers shared by every assigned architecture.

Everything is a pure function over explicit parameter dicts (no framework
modules), so graphs stay small under scan-over-layers and sharding is fully
controlled by the caller.  Attention is a chunked, online-softmax ("flash")
formulation in pure JAX — at 32k prefill a materialised score matrix would be
tens of GB per device, so the chunked path is the only runnable one; XLA maps
each chunk's matmuls onto the MXU.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.params import ParamInfo
from repro.utils.config import ModelConfig

NEG_INF = -2.0e38


# ----------------------------------------------------------------------
# normalisation + positional encoding
# ----------------------------------------------------------------------
def rmsnorm_info(d: int) -> ParamInfo:
    return ParamInfo((d,), ("embed",), init="ones")


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * weight


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: [B, S, H, hd]; positions: [S] or [B, S]."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                    # [hd/2]
    if positions.ndim == 1:
        angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
        angles = angles[None, :, None, :]                  # [1, S, 1, hd/2]
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs
        angles = angles[:, :, None, :]                     # [B, S, 1, hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# chunked online-softmax attention
# ----------------------------------------------------------------------
# Set True (via set_inner_unroll) for dry-run *cost* compiles: inner KV/SSD
# chunk scans fully unroll so XLA cost analysis counts every chunk (while
# bodies are otherwise counted once).  The full-config memory-proof compiles
# keep the rolled loops.
INNER_SCAN_UNROLL = False

# §Perf knobs (set by the perf harness before lowering):
#  FLASH_BF16        — keep flash-attention operands in bf16 (f32 accumulation
#                      via preferred_element_type); halves score-side HBM and
#                      resharding traffic vs the all-f32 baseline.
#  CACHE_UPDATE_MASKED — decode-cache write via one-hot select instead of
#                      dynamic-update-slice: a DUS on a sequence-sharded cache
#                      makes GSPMD replicate the whole cache ("involuntary
#                      full rematerialization"); the masked form is purely
#                      elementwise and stays sharded.
FLASH_BF16 = False
CACHE_UPDATE_MASKED = False

#  DECODE_SHARD — (mesh, batch_axes) or None.  When set, decode attention
#  over a sequence-sharded KV cache runs as explicit flash-decoding under
#  shard_map: local partial softmax per seq shard + pmax/psum combine
#  (~0.2 MB collectives/layer) instead of GSPMD's full-cache all-gather
#  (~1 GB/layer measured on starcoder2 decode_32k).
DECODE_SHARD = None


def set_inner_unroll(flag: bool) -> None:
    global INNER_SCAN_UNROLL
    INNER_SCAN_UNROLL = bool(flag)


def set_flash_bf16(flag: bool) -> None:
    global FLASH_BF16
    FLASH_BF16 = bool(flag)


def set_cache_update_masked(flag: bool) -> None:
    global CACHE_UPDATE_MASKED
    CACHE_UPDATE_MASKED = bool(flag)


def set_decode_shard(mesh, batch_axes=("data",)) -> None:
    global DECODE_SHARD
    DECODE_SHARD = (mesh, tuple(batch_axes)) if mesh is not None else None


def _flash_decode_sharded(q: jnp.ndarray, cache_k: jnp.ndarray,
                          cache_v: jnp.ndarray, valid: jnp.ndarray
                          ) -> jnp.ndarray:
    """Explicit flash-decoding over a seq-sharded cache (see DECODE_SHARD).

    q: [B, 1, H, hd]; cache_k/v: [B, S, KV, hd] (S sharded over `model`);
    valid: [B, S].  Returns [B, 1, H, hd].
    """
    from jax.sharding import PartitionSpec as PS

    mesh, ba = DECODE_SHARD
    b, _, h, hd = q.shape
    kv = cache_k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    bspec = ba if b % int(np.prod([mesh.shape[a] for a in ba])) == 0 else None

    def local(qf, k_l, v_l, valid_l):
        # grouped-query einsum: NO materialised KV expansion — inside
        # shard_map the [KV, G] split is local, so the repeat() that the
        # GSPMD path needed (32 GB/device of expanded f32 K/V on starcoder2
        # decode) is unnecessary.  K/V stay bf16; scores accumulate in f32.
        bq = qf.shape[0]
        q_g = (qf.astype(jnp.float32) * scale).reshape(bq, 1, kv, g, hd)
        s = jnp.einsum("bqkgd,bskd->bqkgs", q_g,
                       k_l.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid_l[:, None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_g[..., None])
        denom = jax.lax.psum(jnp.sum(p, axis=-1), "model")
        pv = jnp.einsum("bqkgs,bskd->bqkgd", p, v_l.astype(jnp.float32))
        acc = jax.lax.psum(pv, "model")
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return out.reshape(bq, 1, h, hd).astype(qf.dtype)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PS(bspec, None, None, None),      # q replicated over model
                  PS(bspec, "model", None, None),   # cache: seq-sharded
                  PS(bspec, "model", None, None),
                  PS(bspec, "model")),
        out_specs=PS(bspec, None, None, None),
        check_vma=False)
    return fn(q, cache_k, cache_v, valid)


def _cache_write(cache: jnp.ndarray, new: jnp.ndarray,
                 pos: jnp.ndarray) -> jnp.ndarray:
    """Write one token at ``pos`` along axis 1 of a [B, S, ...] cache."""
    if not CACHE_UPDATE_MASKED:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), pos, axis=1)
    s_max = cache.shape[1]
    onehot = (jnp.arange(s_max) == pos).reshape(
        (1, s_max) + (1,) * (cache.ndim - 2))
    return jnp.where(onehot, new.astype(cache.dtype), cache)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool, q_offset: int = 0,
                    kv_chunk: int = 2048,
                    kv_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Online-softmax attention, scanned over KV chunks.

    q: [B, Sq, H, hd]; k/v: [B, Skv, KV, hd] (grouped-query: H = KV * G).
    KV heads are expanded to H *per chunk inside the scan body* — the
    transient is one chunk, and the einsum operands keep a clean
    heads-sharded layout under GSPMD (no [KV, G] split dims to re-shard).
    q_offset: absolute position of q[0] (causal masking in decode/chunked
    prefill).  kv_valid: [B, Skv] bool cache-validity mask.
    Returns [B, Sq, H, hd] in q.dtype; softmax in fp32.
    """
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = hd ** -0.5
    nchunks = max(skv // kv_chunk, 1)
    chunk = skv // nchunks
    assert skv % nchunks == 0, (skv, nchunks)

    op_dtype = jnp.bfloat16 if FLASH_BF16 else jnp.float32
    qf = (q.astype(jnp.float32) * scale).astype(op_dtype)  # [B, Sq, H, hd]
    q_pos = q_offset + jnp.arange(sq)

    def expand(t):
        return jnp.repeat(t, g, axis=2) if g > 1 else t

    def step(acc, m, denom, k_c, v_c, kpos_c, valid_c):
        k_e = expand(k_c).astype(op_dtype)                 # [B, c, H, hd]
        v_e = expand(v_c).astype(op_dtype)
        s = jnp.einsum("bqhd,bchd->bqhc", qf, k_e,
                       preferred_element_type=jnp.float32)
        mask = valid_c[:, None, None, :]
        if causal:
            cm = q_pos[:, None] >= kpos_c[None, :]         # [Sq, chunk]
            mask = mask & cm[None, :, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        denom = denom * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhc,bchd->bqhd", p.astype(op_dtype), v_e,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return acc, m_new, denom

    if kv_valid is None:
        kv_valid = jnp.ones((b, skv), bool)

    hd_v = v.shape[-1]                        # MLA: v head dim != qk head dim
    acc0 = jnp.zeros((b, sq, h, hd_v), jnp.float32)
    m0 = jnp.full((b, sq, h), NEG_INF, jnp.float32)
    d0 = jnp.zeros((b, sq, h), jnp.float32)

    if nchunks == 1:
        acc, m, denom = step(acc0, m0, d0, k, v, jnp.arange(skv), kv_valid)
    else:
        k_r = k.reshape(b, nchunks, chunk, kv, k.shape[-1]).transpose(1, 0, 2, 3, 4)
        v_r = v.reshape(b, nchunks, chunk, kv, hd_v).transpose(1, 0, 2, 3, 4)
        kpos = jnp.arange(skv).reshape(nchunks, chunk)
        valid_r = kv_valid.reshape(b, nchunks, chunk).transpose(1, 0, 2)

        def body(carry, inputs):
            return step(*carry, *inputs), None

        # checkpoint the chunk body: score matrices are NEVER saved for the
        # backward pass (flash-attention backward recomputes them).  Without
        # this, a `dots` remat policy would stash every [Sq, chunk] score
        # tile and blow HBM at 32k sequence lengths.
        (acc, m, denom), _ = jax.lax.scan(jax.checkpoint(body), (acc0, m0, d0),
                                          (k_r, v_r, kpos, valid_r),
                                          unroll=INNER_SCAN_UNROLL or 1)
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    return out.astype(q.dtype)


# ----------------------------------------------------------------------
# grouped-query attention (GQA / MQA / MHA)
# ----------------------------------------------------------------------
def gqa_infos(cfg: ModelConfig) -> Dict[str, ParamInfo]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ParamInfo((d, h, hd), ("embed", "heads", "hd")),
        "wk": ParamInfo((d, kv, hd), ("embed", "kv_heads", "hd")),
        "wv": ParamInfo((d, kv, hd), ("embed", "kv_heads", "hd")),
        "wo": ParamInfo((h, hd, d), ("heads", "hd", "embed")),
    }


def gqa_project_kv(p, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    return k, v


def gqa_attention(p, x: jnp.ndarray, cfg: ModelConfig, *, causal: bool = True,
                  positions: Optional[jnp.ndarray] = None,
                  kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                  kv_valid: Optional[jnp.ndarray] = None,
                  q_offset: int = 0, kv_chunk: int = 2048) -> jnp.ndarray:
    """Full-sequence GQA (train / prefill / encoder / cross-attention).

    kv_override: use externally produced (k, v) — cross-attention or cache.
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dqh->bsqh", x, p["wq"])
    if kv_override is None:
        k, v = gqa_project_kv(p, x)
    else:
        k, v = kv_override
    if positions is None:
        positions = jnp.arange(s)
    if cfg.use_rope and kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.use_rope:
        q = apply_rope(q, q_offset + jnp.arange(s), cfg.rope_theta)
    out = flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                          kv_chunk=kv_chunk, kv_valid=kv_valid)
    return jnp.einsum("bsqh,qhd->bsd", out, p["wo"])


def gqa_prefill(p, x: jnp.ndarray, cfg: ModelConfig, *,
                kv_chunk: int = 2048):
    """Causal attention over the prompt, returning (out, k, v) for caching.

    The returned k is post-RoPE — exactly what ``gqa_decode`` appends to.
    """
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dqh->bsqh", x, p["wq"])
    k, v = gqa_project_kv(p, x)
    if cfg.use_rope:
        positions = jnp.arange(s)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = flash_attention(q, k, v, causal=True, kv_chunk=kv_chunk)
    return jnp.einsum("bsqh,qhd->bsd", out, p["wo"]), k, v


def gqa_decode(p, x: jnp.ndarray, cache_k: jnp.ndarray, cache_v: jnp.ndarray,
               cache_len: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, ...]:
    """One-token decode against a [B, S_max, KV, hd] cache.

    Returns (out, new_k, new_v): caches updated at position cache_len.
    """
    b, one, _ = x.shape
    q = jnp.einsum("bsd,dqh->bsqh", x, p["wq"])
    k_new = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v_new = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    if cfg.use_rope:
        pos = jnp.full((1,), cache_len, dtype=jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    cache_k = _cache_write(cache_k, k_new, cache_len)
    cache_v = _cache_write(cache_v, v_new, cache_len)
    s_max = cache_k.shape[1]
    valid = (jnp.arange(s_max) <= cache_len)[None, :] \
        * jnp.ones((b, 1), bool)
    if DECODE_SHARD is not None \
            and s_max % DECODE_SHARD[0].shape["model"] == 0:
        out = _flash_decode_sharded(q, cache_k, cache_v, valid)
    else:
        # single chunk — scores [B,1,H,S]; NOTE (measured): GSPMD gathers
        # the full seq-sharded cache here; prefer DECODE_SHARD on a mesh.
        out = flash_attention(q, cache_k, cache_v, causal=False,
                              kv_valid=valid, kv_chunk=s_max)
    out = jnp.einsum("bsqh,qhd->bsd", out, p["wo"])
    return out, cache_k, cache_v


# ----------------------------------------------------------------------
# multi-head latent attention (MLA — minicpm3 / deepseek-v2 style)
# ----------------------------------------------------------------------
def mla_infos(cfg: ModelConfig) -> Dict[str, ParamInfo]:
    d, h = cfg.d_model, cfg.num_heads
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "q_down": ParamInfo((d, ql), ("embed", "lora")),
        "q_up": ParamInfo((ql, h, dn + dr), ("lora", "heads", "hd")),
        "kv_down": ParamInfo((d, kl + dr), ("embed", "lora")),
        "kv_up": ParamInfo((kl, h, dn + dv), ("lora", "heads", "hd")),
        "wo": ParamInfo((h, dv, d), ("heads", "hd", "embed")),
    }


def _mla_qkv(p, x, cfg: ModelConfig, positions):
    """Project to per-head q/k/v from the compressed latents."""
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kl = cfg.kv_lora_rank
    q = jnp.einsum("bsd,dl,lqh->bsqh", x, p["q_down"], p["q_up"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = jnp.einsum("bsd,dl->bsl", x, p["kv_down"])       # [B,S,kl+dr]
    c, k_rope = ckv[..., :kl], ckv[..., kl:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    kv = jnp.einsum("bsl,lqh->bsqh", c, p["kv_up"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_rope_b = jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (dr,))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    return q_full, k_full, v, ckv


def mla_attention(p, x: jnp.ndarray, cfg: ModelConfig, *,
                  q_offset: int = 0, kv_chunk: int = 2048) -> jnp.ndarray:
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v, _ = _mla_qkv(p, x, cfg, positions)
    out = flash_attention(q, k, v, causal=True, q_offset=q_offset,
                          kv_chunk=kv_chunk)
    return jnp.einsum("bsqh,qhd->bsd", out, p["wo"])


def mla_prefill(p, x: jnp.ndarray, cfg: ModelConfig, *, kv_chunk: int = 2048):
    """MLA prefill returning (out, ckv_store [B, S, kl+dr]).

    The stored latent is [compressed c, post-RoPE k_rope] — the exact layout
    ``mla_decode`` appends to and re-expands.
    """
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v, ckv = _mla_qkv(p, x, cfg, positions)
    kl = cfg.kv_lora_rank
    c, k_rope_raw = ckv[..., :kl], ckv[..., kl:]
    k_roped = apply_rope(k_rope_raw[:, :, None, :], positions,
                         cfg.rope_theta)[:, :, 0, :]
    ckv_store = jnp.concatenate([c, k_roped], axis=-1)
    out = flash_attention(q, k, v, causal=True, kv_chunk=kv_chunk)
    return jnp.einsum("bsqh,qhd->bsd", out, p["wo"]), ckv_store


def mla_decode(p, x: jnp.ndarray, cache_ckv: jnp.ndarray,
               cache_len: jnp.ndarray, cfg: ModelConfig):
    """MLA decode with the *compressed* cache [B, S_max, kl + dr].

    The latent cache is MLA's point: per token only kl+dr floats are stored;
    k/v are re-expanded per step through kv_up (a matmul against the cache).
    """
    b = x.shape[0]
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kl = cfg.kv_lora_rank
    pos = jnp.full((1,), cache_len, dtype=jnp.int32)
    q = jnp.einsum("bsd,dl,lqh->bsqh", x, p["q_down"], p["q_up"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    ckv_new = jnp.einsum("bsd,dl->bsl", x, p["kv_down"])
    c_new, kr_new = ckv_new[..., :kl], ckv_new[..., kl:]
    kr_new = apply_rope(kr_new[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
    ckv_store = jnp.concatenate([c_new, kr_new], axis=-1)
    cache_ckv = _cache_write(cache_ckv, ckv_store, cache_len)

    c_all = cache_ckv[..., :kl]
    kr_all = cache_ckv[..., kl:]
    kv = jnp.einsum("bsl,lqh->bsqh", c_all, p["kv_up"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                  k_nope.shape[:-1] + (dr,))], axis=-1)
    s_max = cache_ckv.shape[1]
    valid = (jnp.arange(s_max) <= cache_len)[None, :] * jnp.ones((b, 1), bool)
    # single-KV-group layout for flash_attention: [B, S, H, hd] per head
    out = flash_attention(q_full, k_full, v, causal=False, kv_valid=valid,
                          kv_chunk=s_max)
    out = jnp.einsum("bsqh,qhd->bsd", out, p["wo"])
    return out, cache_ckv


# ----------------------------------------------------------------------
# MLPs + embedding
# ----------------------------------------------------------------------
def swiglu_infos(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamInfo]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w_gate": ParamInfo((d, f), ("embed", "ff")),
        "w_up": ParamInfo((d, f), ("embed", "ff")),
        "w_down": ParamInfo((f, d), ("ff", "embed")),
    }


def swiglu(p, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def embedding_infos(cfg: ModelConfig) -> Dict[str, ParamInfo]:
    return {
        "tok": ParamInfo((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                         scale=1.0 / (cfg.d_model ** 0.5)),
        "out": ParamInfo((cfg.d_model, cfg.vocab_size), ("embed", "vocab")),
        "final_norm": rmsnorm_info(cfg.d_model),
    }


def embed(p, tokens: jnp.ndarray) -> jnp.ndarray:
    return p["tok"][tokens]


def unembed(p, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(x, p["final_norm"])
    return jnp.einsum("bsd,dv->bsv", x, p["out"])
