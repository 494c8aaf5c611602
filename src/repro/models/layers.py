"""Model layers: norms, RoPE, attention (GQA / qk-norm / sliding window /
NoPE), SwiGLU & GeGLU MLPs, expert-parallel MoE, Mamba-1 and RG-LRU blocks.

All layers are pure functions over parameter pytrees.  Distribution is
expressed with the logical-axis annotations from
:mod:`repro.models.sharding`; the MoE FFN additionally uses ``shard_map``
for deterministic expert parallelism (see ``moe_fwd``).

Attention picks one of three evaluation strategies:

* ``ref`` dense einsum — small shapes (smoke tests, decode steps);
* ``chunked`` — pure-jnp online-softmax double-scan over (q, kv) blocks.
  This is the memory-bounded path the 32k-prefill dry-runs lower
  (per-step temporaries are (B, H, bq, bk), never (B, H, T, S));
* ``kernel`` — the Pallas flash kernel (TPU execution path).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..kernels import ref as kref
from .config import ArchConfig, Block
from .sharding import shard

Params = Dict[str, Any]

_INIT_SCALE = 1.0


def _dense_init(key, shape, in_axis_size, dtype=jnp.float32):
    std = _INIT_SCALE / np.sqrt(in_axis_size)
    return (jax.random.normal(key, shape) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, key) -> Params:
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,))}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,)), "bias": jnp.zeros((cfg.d_model,))}
    if cfg.norm == "nonparam_ln":  # OLMo: LN without learnable params
        return {}
    raise ValueError(cfg.norm)


def apply_norm(cfg: ArchConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        return (xf / rms * p["scale"]).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) / jnp.sqrt(var + 1e-5)
    if cfg.norm == "layernorm":
        out = out * p["scale"] + p["bias"]
    return out.astype(x.dtype)


def _rms_headwise(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (xf / rms * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embedding
# ---------------------------------------------------------------------------

def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, H, T, Dh); positions: (B, T) or (T,)."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,T,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ArchConfig, key) -> Params:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, H, Dh), d),
        "wk": _dense_init(ks[1], (d, Hkv, Dh), d),
        "wv": _dense_init(ks[2], (d, Hkv, Dh), d),
        "wo": _dense_init(ks[3], (H, Dh, d), H * Dh),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((Dh,))
        p["k_scale"] = jnp.ones((Dh,))
    return p


def chunked_attention(
    q, k, v, causal: bool, window: Optional[int], q_offset: int,
    block_q: int = 512, block_k: int = 512,
) -> jnp.ndarray:
    """Online-softmax attention in pure jnp: double lax.scan over q and kv
    blocks; temporaries are (B, H, bq, bk).  Matches kref.attention_ref."""
    B, Hq, T, Dh = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    scale = Dh**-0.5
    bq = min(block_q, T)
    bk = min(block_k, S)
    Tp, Sp = -(-T // bq) * bq, -(-S // bk) * bk
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    nq, nk = Tp // bq, Sp // bk
    qb = q.reshape(B, Hkv, group, nq, bq, Dh).astype(jnp.float32)
    kb = k.reshape(B, Hkv, nk, bk, Dh).astype(jnp.float32)
    vb = v.reshape(B, Hkv, nk, bk, Dh).astype(jnp.float32)

    def q_step(_, qi):
        qc = qb[:, :, :, qi]  # (B, Hkv, G, bq, Dh)
        q_pos = qi * bq + jnp.arange(bq)[:, None] + q_offset  # (bq, 1)

        @functools.partial(
            jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable
        )
        def kv_step(carry, ki):
            m, l, acc = carry
            kc, vc = kb[:, :, ki], vb[:, :, ki]  # (B, Hkv, bk, Dh)
            s = jnp.einsum("bkgqd,bksd->bkgqs", qc, kc) * scale
            k_pos = ki * bk + jnp.arange(bk)[None, :]  # (1, bk)
            mask = k_pos < S
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window is not None:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            alpha = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
            p = jnp.exp(s - m_safe)
            p = jnp.where(mask[None, None, None], p, 0.0)
            l_new = alpha * l + p.sum(axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bkgqs,bksd->bkgqd", p, vc)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, Hkv, group, bq, 1), -jnp.inf),
            jnp.zeros((B, Hkv, group, bq, 1)),
            jnp.zeros((B, Hkv, group, bq, Dh)),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        out = acc / jnp.where(l == 0.0, 1.0, l)
        return None, out

    # checkpoint at both scan levels: the backward pass recomputes each
    # (q, kv) tile's logits instead of stacking (nq, nk, ..., bq, bk) f32
    # score tensors — the flash-attention recompute strategy, in jnp.
    q_step = jax.checkpoint(q_step, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))  # (nq, B, Hkv, G, bq, Dh)
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hkv, group, Tp, Dh)
    out = out.reshape(B, Hq, Tp, Dh)[:, :, :T]
    return out.astype(q.dtype)


#: attention strategy thresholds (elements of the dense logits tensor)
_DENSE_LOGITS_LIMIT = 1 << 27  # ~134M f32 logits = 512 MB


def attention_fwd(
    cfg: ArchConfig,
    blk: Block,
    p: Params,
    x: jnp.ndarray,  # (B, T, d)
    positions: jnp.ndarray,  # (B, T)
    cache: Optional[Dict] = None,
    use_kernel: bool = False,
    mode: str = "train",  # train | prefill | decode
    max_cache_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, T, d = x.shape
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, p["wv"])
    q = shard(q, "act_batch", "act_heads", "act_seq", None)
    k = shard(k, "act_batch", "act_kv_heads", "act_seq", None)
    v = shard(v, "act_batch", "act_kv_heads", "act_seq", None)
    if cfg.qk_norm:
        q = _rms_headwise(q, p["q_scale"])
        k = _rms_headwise(k, p["k_scale"])
    if blk.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if mode == "decode":
        # per-row write positions: each batch row (serving slot) may sit at
        # a different absolute position — required for continuous batching.
        assert cache is not None
        quantized = "k_scale" in cache
        Hkv = k.shape[1]
        b_idx = jnp.arange(B)[:, None, None]
        h_idx = jnp.arange(Hkv)[None, :, None]
        pos_idx = positions[:, None, :]  # (B, 1, T)
        if quantized:
            # int8 KV cache (§Perf): halves the per-token cache sweep.
            kq, ks = _quant_kv(k)
            vq, vs = _quant_kv(v)
            k_all = cache["k"].at[b_idx, h_idx, pos_idx].set(kq)
            v_all = cache["v"].at[b_idx, h_idx, pos_idx].set(vq)
            ks_all = cache["k_scale"].at[b_idx, h_idx, pos_idx].set(ks)
            vs_all = cache["v_scale"].at[b_idx, h_idx, pos_idx].set(vs)
            new_cache = {"k": k_all, "v": v_all,
                         "k_scale": ks_all, "v_scale": vs_all}
            k = _dequant_kv(k_all, ks_all, x.dtype)
            v = _dequant_kv(v_all, vs_all, x.dtype)
        else:
            k_all = cache["k"].at[b_idx, h_idx, pos_idx].set(k)
            v_all = cache["v"].at[b_idx, h_idx, pos_idx].set(v)
            new_cache = {"k": k_all, "v": v_all}
            k, v = k_all, v_all
    elif mode == "prefill":
        S_max = max_cache_len or T
        pad = S_max - T
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
        new_cache = {"k": kc, "v": vc}

    S = k.shape[2]
    dense_cost = B * cfg.n_heads * T * S
    if mode == "decode":
        # decode path: T is tiny; dense einsum over the cache, masked by
        # each row's absolute positions (traced).
        out = _decode_attention(q, k, v, positions, blk.window)
    elif use_kernel:
        out = kops.attention(
            q, k, v, causal=True, window=blk.window, q_offset=0
        )
    elif dense_cost <= _DENSE_LOGITS_LIMIT:
        out = kref.attention_ref(q, k, v, causal=True, window=blk.window)
    else:
        out = chunked_attention(q, k, v, True, blk.window, 0)
    out = shard(out, "act_batch", "act_heads", "act_seq", None)
    y = jnp.einsum("bhtk,hkd->btd", out, p["wo"])
    return shard(y, "act_batch", "act_seq", "act_embed"), new_cache


def _quant_kv(x: jnp.ndarray):
    """Per-(row, head, position) int8 quantization over the head dim."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _dequant_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _decode_attention(q, k, v, positions, window):
    """Dense attention against a (zero-padded) cache; ``positions`` (B, T)
    are the traced absolute positions of the queries (per serving slot)."""
    B, Hq, Tq, Dh = q.shape
    _, Hkv, S, _ = k.shape
    group = Hq // Hkv
    scale = Dh**-0.5
    qg = q.reshape(B, Hkv, group, Tq, Dh).astype(jnp.float32)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k.astype(jnp.float32)) * scale
    q_pos = positions[:, :, None]  # (B, T, 1)
    k_pos = jnp.arange(S)[None, None, :]
    mask = k_pos <= q_pos  # (B, T, S)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Tq, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], (d, f), d),
        "w_up": _dense_init(ks[1], (d, f), d),
        "w_down": _dense_init(ks[2], (f, d), f),
    }


def _act(cfg: ArchConfig, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def mlp_fwd(cfg: ArchConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("btd,df->btf", x, p["w_gate"])
    u = jnp.einsum("btd,df->btf", x, p["w_up"])
    h = shard(_act(cfg, g) * u, "act_batch", "act_seq", "act_ffn")
    y = jnp.einsum("btf,fd->btd", h, p["w_down"])
    return shard(y, "act_batch", "act_seq", "act_embed")


# ---------------------------------------------------------------------------
# MoE (expert-parallel, capacity-dropped, optionally geo-planned)
# ---------------------------------------------------------------------------

def _pad_experts(cfg: ArchConfig, tp: int) -> int:
    """Experts padded up to a multiple of the TP degree (zero router mass)."""
    E = cfg.n_experts
    return -(-E // tp) * tp


def init_moe(cfg: ArchConfig, key, tp: int = 1) -> Params:
    d, f = cfg.d_model, cfg.expert_d_ff_
    Ep = _pad_experts(cfg, tp)
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, Ep), d),
        "w_gate": _dense_init(ks[1], (Ep, d, f), d),
        "w_up": _dense_init(ks[2], (Ep, d, f), d),
        "w_down": _dense_init(ks[3], (Ep, f, d), f),
        # planned per-expert capacity fractions / router bias (repro.core.
        # moe_plan): identity by default, loaded by the launcher when a
        # dispatch plan is active.  Padding experts (beyond n_experts) are
        # masked with a -inf-ish bias so they never receive tokens — padding
        # is exact, only the wasted FLOPs show up in the roofline ratio.
        "plan_bias": jnp.where(jnp.arange(Ep) < cfg.n_experts, 0.0, -1e9),
        "plan_capacity": jnp.ones((Ep,)),
    }


def _moe_local(cfg: ArchConfig, p: Params, x2d: jnp.ndarray):
    """Token dispatch + expert FFN over all experts on one device.

    x2d: (N, d) tokens.  Returns (y (N, d), aux_loss).
    """
    N, d = x2d.shape
    E_here = p["w_gate"].shape[0]
    logits = x2d @ p["router"] + p["plan_bias"]
    # mask padded experts (zero-initialized plan_capacity == 1; padded
    # experts carry -inf bias set at init-load time via router masking)
    gates_all = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_gates, top_ids = jax.lax.top_k(gates_all, cfg.top_k)  # (N, k)
    top_gates = top_gates / jnp.maximum(
        top_gates.sum(axis=-1, keepdims=True), 1e-9
    )
    # load-balance aux loss (Switch): E * Σ_e f_e · P_e
    pe = gates_all.mean(axis=0)
    fe = jnp.zeros((E_here,)).at[top_ids.reshape(-1)].add(
        jnp.ones((N * cfg.top_k,)) / (N * cfg.top_k)
    )
    aux = E_here * jnp.sum(pe * fe)

    cap = jnp.asarray(p["plan_capacity"][:E_here])
    C = int(np.ceil(N * cfg.top_k / E_here * cfg.capacity_factor))
    C = max(C, cfg.top_k)
    flat_ids = top_ids.reshape(-1)  # (N*k,)
    flat_gates = top_gates.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(N), cfg.top_k)
    slots = kops.compute_slots(flat_ids, E_here)
    # planned capacity: expert e accepts plan_capacity[e] * C tokens
    cap_e = jnp.clip(jnp.round(cap * C), 1, None).astype(jnp.int32)
    keep = slots < cap_e[flat_ids]
    buf = jnp.zeros((E_here, C, d), x2d.dtype)
    safe_ids = jnp.where(keep, flat_ids, 0)
    safe_slots = jnp.where(keep, jnp.minimum(slots, C - 1), 0)
    buf = buf.at[safe_ids, safe_slots].add(
        jnp.where(keep[:, None], x2d[tok_idx], 0.0)
    )
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = _act(cfg, h) * u
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    back = out[safe_ids, safe_slots]
    back = jnp.where(keep[:, None], back, 0.0) * flat_gates[:, None]
    y = jnp.zeros((N, d), x2d.dtype).at[tok_idx].add(back.astype(x2d.dtype))
    return y, aux


def moe_fwd(
    cfg: ArchConfig,
    p: Params,
    x: jnp.ndarray,  # (B, T, d)
    mesh=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE.  With a mesh: shard_map over (pod, data) for
    tokens and 'model' for experts — every device dispatches its local
    tokens to its local experts and contributions are psum'd over 'model'
    (deterministic EP without all_to_all; the dispatch *plan* from
    repro.core.moe_plan reweights per-expert capacity).  Without a mesh:
    single-device dispatch over all experts."""
    B, T, d = x.shape
    if mesh is None or "model" not in mesh.axis_names:
        y2d, aux = _moe_local(cfg, p, x.reshape(B * T, d))
        return y2d.reshape(B, T, d), aux

    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local(xl, router, bias, capf, wg, wu, wd):
        Bl, Tl, _ = xl.shape
        pl = {
            "router": router, "plan_bias": bias, "plan_capacity": capf,
            "w_gate": wg, "w_up": wu, "w_down": wd,
        }
        # router over *all* experts, dispatch to the local shard only:
        # tokens whose expert lives elsewhere contribute nothing here and
        # are summed in via the psum.
        E = router.shape[1]
        El = wg.shape[0]
        shard_idx = jax.lax.axis_index("model")
        lo = shard_idx * El
        logits = (xl.reshape(Bl * Tl, d) @ router) + bias
        gates_all = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_gates, top_ids = jax.lax.top_k(gates_all, cfg.top_k)
        top_gates = top_gates / jnp.maximum(
            top_gates.sum(axis=-1, keepdims=True), 1e-9
        )
        pe = gates_all.mean(axis=0)
        N = Bl * Tl
        fe = jnp.zeros((E,)).at[top_ids.reshape(-1)].add(
            jnp.ones((N * cfg.top_k,)) / (N * cfg.top_k)
        )
        # aggregate the load statistics over the data shards FIRST, so the
        # aux loss equals the single-device (global-batch) definition
        if batch_axes:
            pe = jax.lax.pmean(pe, batch_axes)
            fe = jax.lax.pmean(fe, batch_axes)
        aux = E * jnp.sum(pe * fe)
        # localize: expert ids relative to this shard; non-local -> dropped
        flat_ids = top_ids.reshape(-1) - lo
        local_mask = (flat_ids >= 0) & (flat_ids < El)
        flat_gates = jnp.where(local_mask, top_gates.reshape(-1), 0.0)
        flat_ids = jnp.clip(flat_ids, 0, El - 1)
        tok_idx = jnp.repeat(jnp.arange(N), cfg.top_k)
        C = int(np.ceil(N * cfg.top_k / E * cfg.capacity_factor))
        C = max(C, cfg.top_k)
        cap_e = jnp.clip(jnp.round(capf * C), 1, None).astype(jnp.int32)
        # slots computed over local assignment stream (masked entries get
        # slot C so they never land)
        ids_for_slots = jnp.where(local_mask, flat_ids, El)
        slots = kops.compute_slots(ids_for_slots, El + 1)
        keep = local_mask & (slots < cap_e[flat_ids])
        safe_slots = jnp.where(keep, jnp.minimum(slots, C - 1), 0)
        safe_ids = jnp.where(keep, flat_ids, 0)
        buf = jnp.zeros((El, C, d), xl.dtype)
        buf = buf.at[safe_ids, safe_slots].add(
            jnp.where(keep[:, None], xl.reshape(N, d)[tok_idx], 0.0)
        )
        h = _act(cfg, jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
            "ecd,edf->ecf", buf, wu
        )
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        back = out[safe_ids, safe_slots]
        back = jnp.where(keep[:, None], back, 0.0) * flat_gates[:, None]
        y = jnp.zeros((N, d), xl.dtype).at[tok_idx].add(back.astype(xl.dtype))
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")  # identical on every model shard
        return y.reshape(Bl, Tl, d), aux

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    yl, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None), P(None), P("model"),
            P("model", None, None), P("model", None, None), P("model", None, None),
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(
        x, p["router"], p["plan_bias"], p["plan_capacity"],
        p["w_gate"], p["w_up"], p["w_down"],
    )
    return yl, aux


# ---------------------------------------------------------------------------
# Mamba-1 block
# ---------------------------------------------------------------------------

def init_mamba(cfg: ArchConfig, key) -> Params:
    d, di, ds, dtr = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_dt_rank_
    ks = jax.random.split(key, 6)
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * di), d),
        "conv": _dense_init(ks[1], (cfg.ssm_conv, di), cfg.ssm_conv),
        "x_proj": _dense_init(ks[2], (di, dtr + 2 * ds), di),
        "dt_proj": _dense_init(ks[3], (dtr, di), dtr),
        "dt_bias": jnp.zeros((di,)) + jnp.log(jnp.expm1(0.01)),  # softplus^-1
        "A_log": jnp.log(
            jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None], (di, 1))
        ),
        "D": jnp.ones((di,)),
        "out_proj": _dense_init(ks[5], (di, d), di),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, prev: Optional[jnp.ndarray]):
    """Depthwise causal conv along time.  x: (B, T, C); w: (K, C);
    prev: (B, K-1, C) carried context (decode) or None (zeros)."""
    B, T, C = x.shape
    K = w.shape[0]
    if prev is None:
        prev = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)  # (B, T+K-1, C)
    out = jnp.zeros((B, T, C), x.dtype)
    for i in range(K):  # K is tiny (4): unrolled taps, no conv primitive
        out = out + xp[:, i : i + T] * w[i]
    new_prev = xp[:, -(K - 1):] if K > 1 else prev
    return out, new_prev


def mamba_fwd(
    cfg: ArchConfig,
    p: Params,
    x: jnp.ndarray,  # (B, T, d)
    state: Optional[Dict] = None,
    use_kernel: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, T, d = x.shape
    di, ds, dtr = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_dt_rank_
    xz = jnp.einsum("btd,de->bte", x, p["in_proj"])
    xz = shard(xz, "act_batch", "act_seq", "act_ffn")
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_prev = state["conv"] if state is not None else None
    xi, conv_new = _causal_conv(xi, p["conv"], conv_prev)
    xi = jax.nn.silu(xi)
    proj = jnp.einsum("bti,ie->bte", xi, p["x_proj"])
    dt, Bc, Cc = jnp.split(proj, [dtr, dtr + ds], axis=-1)
    delta = jax.nn.softplus(jnp.einsum("btr,ri->bti", dt, p["dt_proj"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    h0 = state["h"] if state is not None else None
    y, hT = kops.ssm_scan(xi, delta, A, Bc, Cc, p["D"], h0, use_kernel=use_kernel)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bti,id->btd", y, p["out_proj"])
    out = shard(out, "act_batch", "act_seq", "act_embed")
    new_state = {"h": hT, "conv": conv_new} if state is not None else None
    return out, new_state


# ---------------------------------------------------------------------------
# RG-LRU block (RecurrentGemma recurrent block)
# ---------------------------------------------------------------------------

def init_rglru(cfg: ArchConfig, key) -> Params:
    d, w = cfg.d_model, cfg.rglru_width
    ks = jax.random.split(key, 5)
    return {
        "in_x": _dense_init(ks[0], (d, w), d),
        "in_gate": _dense_init(ks[1], (d, w), d),
        "conv": _dense_init(ks[2], (4, w), 4),
        "a_gate_w": _dense_init(ks[3], (w,), 1),  # diagonal gates (RG-LRU)
        "a_gate_b": jnp.zeros((w,)) + 2.0,  # init a ≈ sigmoid(2) ≈ .88
        "x_gate_w": _dense_init(ks[4], (w,), 1),
        "out_proj": _dense_init(jax.random.fold_in(key, 7), (w, d), w),
    }


def rglru_fwd(
    cfg: ArchConfig,
    p: Params,
    x: jnp.ndarray,  # (B, T, d)
    state: Optional[Dict] = None,
    use_kernel: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    xb = jnp.einsum("btd,dw->btw", x, p["in_x"])
    gb = jax.nn.gelu(jnp.einsum("btd,dw->btw", x, p["in_gate"]))
    xb = shard(xb, "act_batch", "act_seq", "act_ffn")
    conv_prev = state["conv"] if state is not None else None
    xb, conv_new = _causal_conv(xb, p["conv"], conv_prev)
    # diagonal recurrence and input gates
    a = jax.nn.sigmoid(xb * p["a_gate_w"] + p["a_gate_b"])
    gate_x = jax.nn.sigmoid(xb * p["x_gate_w"])
    h0 = state["h"] if state is not None else None
    h, hT = kops.gated_linear_recurrence(
        xb * gate_x, a, h0, use_kernel=use_kernel
    )
    y = h * gb
    out = jnp.einsum("btw,wd->btd", y, p["out_proj"])
    out = shard(out, "act_batch", "act_seq", "act_embed")
    new_state = {"h": hT, "conv": conv_new} if state is not None else None
    return out, new_state
