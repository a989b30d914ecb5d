"""Composable pure-JAX blocks: attention, MLP, MoE, Mamba-1, RG-LRU.

Every block provides
    init_<block>(pb, p, a, cfg, ...)          — create params + logical axes
    <block>_apply(cfg, p, x, ..., cache=None) — forward (train/prefill/decode)

Conventions:
  * x is (B, S, d).  Decode calls use S == 1 plus a cache.
  * caches are dicts of arrays; attention caches are ring buffers of length
    ``cache_len`` (== window for sliding-window decode, == max-seq else),
    with stored absolute positions for masking, so the same code serves
    full-context decode (decode_32k) and windowed long-context decode
    (long_500k sliding-window variant).
  * logical axes used here: "embed" (d_model), "heads", "kv_heads",
    "head_dim", "mlp" (d_ff), "vocab", "experts", "expert_mlp",
    "ssm_inner", "ssm_state", "dt_rank", "lru", "conv", "layers" (stacking).
  * flags: dict of runtime options; flags["attn_impl"] in
    {"einsum", "chunked"} selects the attention materialization strategy
    (chunked = online-softmax flash-style, used by the perf pass).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig, ParamBuilder, rms_norm, rope

NEG_INF = -1e30


# =============================================================== attention

def init_attention(pb: ParamBuilder, p: dict, a: dict, cfg: ModelConfig,
                   cross: bool = False):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pb.param(p, a, "wq", (d, H, hd), ("embed", "heads", "head_dim"))
    pb.param(p, a, "wk", (d, KV, hd), ("embed", "kv_heads", "head_dim"))
    pb.param(p, a, "wv", (d, KV, hd), ("embed", "kv_heads", "head_dim"))
    pb.param(p, a, "wo", (H, hd, d), ("heads", "head_dim", "embed"))
    if cfg.qk_norm:
        pb.param(p, a, "q_norm", (hd,), ("head_dim",), init="ones")
        pb.param(p, a, "k_norm", (hd,), ("head_dim",), init="ones")


def _qk_normalize(cfg, p, q, k):
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k


def _attend_einsum(q, k, v, mask):
    """q:(B,S,H,hd) k/v:(B,T,KV,hd) mask:(B,1,S,T) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) / jnp.sqrt(hd).astype(q.dtype)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask[:, 0][:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, hd)


def _attend_chunked(q, k, v, mask, chunk: int = 512):
    """Flash-style online softmax over key chunks (no SxT materialization)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    T = k.shape[1]
    G = H // KV
    chunk = min(chunk, T)
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, 0), (0, pad)))
    qg = (q.reshape(B, S, KV, G, hd) / jnp.sqrt(hd).astype(q.dtype))
    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    mc = mask.reshape(B, 1, S, n_chunks, chunk).transpose(3, 0, 1, 2, 4)

    def step(carry, xs):
        m_run, l_run, o_run = carry
        k_i, v_i, msk = xs                      # (B,c,KV,hd), (B,1,S,c)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k_i).astype(jnp.float32)
        s = jnp.where(msk[:, 0][:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(pexp, axis=-1)
        o_i = jnp.einsum("bkgst,btkd->bkgsd", pexp.astype(q.dtype), v_i)
        o_new = o_run * alpha[..., None].astype(q.dtype) + o_i
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    o0 = jnp.zeros((B, KV, G, S, hd), q.dtype)
    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), (kc, vc, mc))
    out = o / jnp.maximum(l, 1e-30)[..., None].astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


def _causal_mask(positions_q: jnp.ndarray, positions_k: jnp.ndarray,
                 window: Optional[int]) -> jnp.ndarray:
    """(B,1,S,T) mask: causal, optionally sliding-window, k-pos >= 0 valid."""
    m = positions_k[:, None, None, :] <= positions_q[:, None, :, None]
    m &= positions_k[:, None, None, :] >= 0
    if window is not None:
        m &= (positions_q[:, None, :, None] - positions_k[:, None, None, :]
              < window)
    return m


def attention_apply(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                    positions: jnp.ndarray, *, kind: str = "global",
                    cache: Optional[dict] = None, mode: str = "train",
                    flags: Optional[dict] = None,
                    cross_kv: Optional[tuple] = None):
    """Self- (or cross-) attention. Returns (y, new_cache)."""
    flags = flags or {}
    B, S, d = x.shape
    window = cfg.window_size if kind == "local" else None
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cross_kv is not None:
        # cross-attention to the encoder memory (B, S_enc, d): K/V computed
        # from the memory, no causal mask, no rope
        k = jnp.einsum("bsd,dhk->bshk", cross_kv, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", cross_kv, p["wv"])
        mask = jnp.ones((B, 1, S, k.shape[1]), bool)
        impl = flags.get("attn_impl", "einsum")
        out = (_attend_chunked if impl == "chunked" else _attend_einsum)(q, k, v, mask)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, cache
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q, k = _qk_normalize(cfg, p, q, k)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if mode == "decode":
        assert cache is not None and S == 1
        L = cache["k"].shape[1]
        slot = (positions[:, 0] % L).astype(jnp.int32)      # ring slot per batch
        bidx = jnp.arange(B)
        ck = cache["k"].at[bidx, slot].set(k[:, 0])
        cv = cache["v"].at[bidx, slot].set(v[:, 0])
        cpos = cache["pos"].at[bidx, slot].set(positions[:, 0].astype(jnp.int32))
        mask = _causal_mask(positions, cpos, window)
        impl = (flags or {}).get("attn_impl", "einsum")
        out = (_attend_chunked if impl == "chunked" else _attend_einsum)(
            q, ck, cv, mask)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return y, {"k": ck, "v": cv, "pos": cpos}

    # train / prefill over the full sequence
    mask = _causal_mask(positions, positions.astype(jnp.int32), window)
    if kind == "encoder":                                    # bidirectional
        mask = jnp.ones_like(mask)
    impl = flags.get("attn_impl", "einsum")
    out = (_attend_chunked if impl == "chunked" else _attend_einsum)(q, k, v, mask)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    new_cache = None
    if mode == "prefill":
        cache_len = flags.get("cache_len", S)
        if cache_len >= S:
            pad = cache_len - S
            ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cpos = jnp.pad(positions.astype(jnp.int32), ((0, 0), (0, pad)),
                           constant_values=-1)
        else:
            # keep only the last `cache_len` keys, scattered to their ring
            # slot (slot = pos % cache_len) so decode writes line up
            ck0, cv0 = k[:, -cache_len:], v[:, -cache_len:]
            cpos0 = positions[:, -cache_len:].astype(jnp.int32)
            bidx = jnp.arange(B)[:, None]
            slots = cpos0 % cache_len
            ck = jnp.zeros_like(ck0).at[bidx, slots].set(ck0)
            cv = jnp.zeros_like(cv0).at[bidx, slots].set(cv0)
            cpos = jnp.full_like(cpos0, -1).at[bidx, slots].set(cpos0)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    return y, new_cache


def init_attention_cache(cfg: ModelConfig, batch: int, cache_len: int,
                         dtype) -> dict:
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, cache_len, KV, hd), dtype),
        "v": jnp.zeros((batch, cache_len, KV, hd), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


# ==================================================================== MLP

def init_mlp(pb, p, a, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    pb.param(p, a, "w_gate", (d, f), ("embed", "mlp"))
    pb.param(p, a, "w_up", (d, f), ("embed", "mlp"))
    pb.param(p, a, "w_down", (f, d), ("mlp", "embed"))


def mlp_apply(cfg, p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ==================================================================== MoE

def init_moe(pb, p, a, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    # the router is replicated ("experts_router" has no sharding rule):
    # routing needs the full expert axis on every shard under EP
    pb.param(p, a, "router", (d, E), ("embed", "experts_router"), scale=0.02)
    pb.param(p, a, "w_gate", (E, d, f), ("experts", "embed", "expert_mlp"))
    pb.param(p, a, "w_up", (E, d, f), ("experts", "embed", "expert_mlp"))
    pb.param(p, a, "w_down", (E, f, d), ("experts", "expert_mlp", "embed"))


def _moe_dispatch(cfg: ModelConfig, router, xf: jnp.ndarray, C: int):
    """Shared routing: returns (buf (E,C,d), combine-info, aux)."""
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    logits = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eids = jax.lax.top_k(probs, k)                     # (T,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
    flat_e = eids.reshape(-1).astype(jnp.int32)                   # (T*k,)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    tok = order // k
    starts = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    valid = pos < C
    dest = se * C + jnp.where(valid, pos, 0)
    src = jnp.where(valid[:, None], xf[tok], jnp.zeros((1, d), xf.dtype))
    buf = jnp.zeros((E * C, d), xf.dtype).at[dest].add(src)
    dispatch_frac = jnp.mean(
        (jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32)), axis=0)
    prob_frac = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(dispatch_frac * prob_frac)
    combine = (tok, dest, valid, gate_vals.reshape(-1)[order])
    return buf.reshape(E, C, d), combine, aux


def _moe_combine(combine, out_buf: jnp.ndarray, T: int, dtype):
    tok, dest, valid, gates = combine
    d = out_buf.shape[-1]
    flat = out_buf.reshape(-1, d)
    gathered = flat[dest] * (valid[:, None] * gates[:, None]).astype(dtype)
    return jnp.zeros((T, d), dtype).at[tok].add(gathered)


def _capacity(cfg: ModelConfig, T: int) -> int:
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    if T * k <= 256:
        # dropless small-batch path (decode): full capacity so routing is
        # exactly consistent with the large-batch forward pass
        return T * k
    return max(1, int(T * k * cfg.moe_capacity_factor / E))


def _expert_ffn(p, buf):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def moe_apply(cfg: ModelConfig, p: dict, x: jnp.ndarray,
              flags: Optional[dict] = None):
    """Top-k MoE with sort-based dispatch and fixed per-expert capacity.

    Two implementations (flags["moe_impl"]):
      * "auto" (default): routing/scatter expressed in plain jnp and left
        to the XLA SPMD partitioner. Correct everywhere, but the scatter
        from token-sharded operands into the expert-sharded buffer lowers
        to a full-buffer all-reduce — the dominant collective cost on MoE
        shapes (see EXPERIMENTS.md §Perf).
      * "ep": explicit expert parallelism — tokens are dispatched into a
        per-source-shard capacity buffer and exchanged with a single
        ``all_to_all`` over the "data" mesh axis (and back), the canonical
        TPU MoE schedule. Requires E %% data-shards == 0. Used via
        ``jax.shard_map`` (serve) or directly when the caller is already
        manual over "data" (the FL train step).
    Returns (y, aux_loss) with the standard switch load-balance auxiliary.
    """
    flags = flags or {}
    impl = flags.get("moe_impl", "auto")
    B, S, d = x.shape
    T = B * S
    if impl == "ep":
        mesh = flags.get("mesh")
        axis = "data"
        quant = bool(flags.get("moe_a2a_quant", False))
        # the FL train step runs the model inside a client-manual shard_map
        # and marks it via flags; there we can all_to_all directly
        if flags.get("_in_manual"):
            return _moe_apply_ep(cfg, p, x, axis, quant=quant)
        if mesh is not None and axis in mesh.axis_names \
                and cfg.n_experts % mesh.shape[axis] == 0 \
                and B % mesh.shape[axis] == 0:
            from jax.sharding import PartitionSpec as P
            pspecs = {"router": P(), "w_gate": P(axis), "w_up": P(axis),
                      "w_down": P(axis)}
            fn = jax.shard_map(
                lambda p_, x_: _moe_apply_ep(cfg, p_, x_, axis, quant=quant),
                mesh=mesh, in_specs=(pspecs, P(axis)),
                out_specs=(P(axis), P()), axis_names={axis},
                check_vma=False)
            return fn(p, x)
        # fall through to auto when EP preconditions fail
    C = _capacity(cfg, T)
    xf = x.reshape(T, d)
    buf, combine, aux = _moe_dispatch(cfg, p["router"], xf, C)
    out_buf = _expert_ffn(p, buf)
    y = _moe_combine(combine, out_buf, T, x.dtype)
    return y.reshape(B, S, d), aux


def _a2a_quantized(t: jnp.ndarray, axis: str):
    """int8-quantized all_to_all: halves the link payload vs bf16 (the
    paper's quantized-uplink idea applied to the EP dispatch). Per-slice
    absmax scales ride along as a tiny side channel. The backward pass is a
    plain all_to_all (straight-through; the a2a permutation is its own
    adjoint for split=concat=0), so the flag is safe under jax.grad."""

    @jax.custom_vjp
    def qa2a(u):
        scale = jnp.max(jnp.abs(u), axis=tuple(range(1, u.ndim)),
                        keepdims=True).astype(jnp.float32)      # (n,1,..)
        q = jnp.clip(jnp.round(u.astype(jnp.float32)
                               / jnp.maximum(scale, 1e-30) * 127.0),
                     -127, 127).astype(jnp.int8)
        q = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
        scale = jax.lax.all_to_all(scale, axis, split_axis=0, concat_axis=0)
        return (q.astype(jnp.float32) * scale / 127.0).astype(u.dtype)

    def fwd(u):
        return qa2a(u), None

    def bwd(_, g):
        return (jax.lax.all_to_all(g, axis, split_axis=0, concat_axis=0),)

    qa2a.defvjp(fwd, bwd)
    return qa2a(t)


def _moe_apply_ep(cfg: ModelConfig, p: dict, x: jnp.ndarray, axis: str,
                  quant: bool = False):
    """Expert-parallel body: local routing -> all_to_all -> local experts ->
    inverse all_to_all -> local combine. Called with "data"-manual scope;
    p holds the LOCAL expert shard (E_loc = E/n_shards)."""
    n = jax.lax.axis_size(axis)
    B, S, d = x.shape
    T = B * S
    E = cfg.n_experts
    E_loc = E // n
    C = _capacity(cfg, T)                      # capacity per (src, expert)
    xf = x.reshape(T, d)
    buf, combine, aux = _moe_dispatch(cfg, p["router"], xf, C)
    # (E, C, d) -> (n, E_loc, C, d) -> exchange -> (n_src, E_loc, C, d)
    buf = buf.reshape(n, E_loc, C, d)
    if quant:
        buf = _a2a_quantized(buf, axis)
    else:
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
    # experts see all sources: (E_loc, n*C, d)
    buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, n * C, d)
    out = _expert_ffn(p, buf)
    # NOTE (§Perf iteration 2, refuted): forcing a d-sharded layout here
    # (with_sharding_constraint P(None,None,"model")) was tried to turn the
    # model-axis all-reduce of this buffer into a reduce-scatter; XLA kept
    # the all-reduce AND added an all-gather (+74% collective bytes).
    # Exploiting the linearity of the combine needs the model axis manual
    # too (full-manual MoE) — left as future work.
    out = out.reshape(E_loc, n, C, d).transpose(1, 0, 2, 3)
    if quant:
        out = _a2a_quantized(out, axis)
    else:
        out = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
    out_buf = out.reshape(E, C, d)
    y = _moe_combine(combine, out_buf, T, x.dtype)
    aux = jax.lax.pmean(aux, axis)
    return y.reshape(B, S, d), aux


# ================================================= chunked linear scans

def linear_scan_chunked(a: jnp.ndarray, b: jnp.ndarray, h0: jnp.ndarray,
                        chunk: int = 128):
    """h_t = a_t * h_{t-1} + b_t elementwise, over axis 1 of (B, S, ...).

    TPU adaptation: sequential lax.scan over chunks (carry in VMEM-sized
    state) with a parallel associative scan inside each chunk — bounds the
    materialized (B, chunk, ...) working set instead of (B, S, ...).
    Returns (h_all (B,S,...), h_last (B,...)).
    """
    B, S = a.shape[0], a.shape[1]
    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad)) + ((0, 0),) * (b.ndim - 2))
    ac = a.reshape((B, n_chunks, chunk) + a.shape[2:]).transpose(
        (1, 0, 2) + tuple(range(3, a.ndim + 1)))
    bc = b.reshape((B, n_chunks, chunk) + b.shape[2:]).transpose(
        (1, 0, 2) + tuple(range(3, b.ndim + 1)))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    def step(h, xs):
        a_i, b_i = xs                       # (B, chunk, ...)
        A, Bv = jax.lax.associative_scan(combine, (a_i, b_i), axis=1)
        h_all = A * h[:, None] + Bv
        return h_all[:, -1], h_all

    h_last, chunks = jax.lax.scan(step, h0, (ac, bc))
    out = chunks.transpose((1, 0, 2) + tuple(range(3, a.ndim + 1)))
    out = out.reshape((B, n_chunks * chunk) + a.shape[2:])[:, :S]
    return out, h_last


# ============================================================ conv1d state

def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, bias: jnp.ndarray,
                  state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv over seq. x:(B,S,D), w:(K,D). Returns (y, state')
    where state' holds the last K-1 inputs for streaming decode."""
    K = w.shape[0]
    B, S, D = x.shape
    if state is None:
        state = jnp.zeros((B, K - 1, D), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)       # (B, S+K-1, D)
    y = sum(xp[:, i:i + S] * w[i] for i in range(K)) + bias
    new_state = xp[:, -(K - 1):] if K > 1 else state
    return y, new_state


# ================================================================= Mamba-1

def init_mamba(pb, p, a, cfg: ModelConfig):
    d, di, n, dr, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                       cfg.ssm_conv)
    pb.param(p, a, "in_proj", (d, 2 * di), ("embed", "ssm_inner"))
    pb.param(p, a, "conv_w", (K, di), ("conv", "ssm_inner"), scale=0.5)
    pb.param(p, a, "conv_b", (di,), ("ssm_inner",), init="zeros")
    pb.param(p, a, "x_proj", (di, dr + 2 * n), ("ssm_inner", "dt_rank"))
    pb.param(p, a, "dt_proj", (dr, di), ("dt_rank", "ssm_inner"))
    pb.param(p, a, "dt_bias", (di,), ("ssm_inner",), init="zeros")
    pb.param(p, a, "a_log", (di, n), ("ssm_inner", "ssm_state"), init="ssm_a")
    pb.param(p, a, "d_skip", (di,), ("ssm_inner",), init="ones")
    pb.param(p, a, "out_proj", (di, d), ("ssm_inner", "embed"))


def _selective_scan_fused(dt, Bmat, xb, A, Cmat, h0, chunk: int):
    """Chunked selective scan with the C-projection FUSED into the chunk
    loop: neither the (B,S,di,n) transition tensors nor the state history
    are materialized over the full sequence — the loop carries h (B,di,n)
    and stores only y (B,S,di). This is the memory-roofline optimization
    recorded in EXPERIMENTS.md §Perf (the same restructuring the Mamba CUDA
    kernel performs in registers, re-thought as a chunked TPU loop).
    """
    B, S, di = dt.shape
    n = A.shape[-1]
    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
        xb = jnp.pad(xb, ((0, 0), (0, pad), (0, 0)))

    def to_chunks(t):
        return t.reshape((B, n_chunks, chunk) + t.shape[2:]).transpose(
            (1, 0, 2) + tuple(range(3, t.ndim + 1)))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    def step(h, xs):
        dt_c, B_c, C_c, x_c = xs                      # (B, c, ...)
        a_c = jnp.exp(dt_c[..., None] * A)            # (B,c,di,n) transient
        b_c = (dt_c[..., None] * B_c[:, :, None, :]
               * x_c.astype(jnp.float32)[..., None])
        A_cum, B_cum = jax.lax.associative_scan(combine, (a_c, b_c), axis=1)
        h_all = A_cum * h[:, None] + B_cum
        y_c = jnp.einsum("bcdn,bcn->bcd", h_all, C_c)
        return h_all[:, -1], y_c

    h_last, ys = jax.lax.scan(
        step, h0, (to_chunks(dt), to_chunks(Bmat), to_chunks(Cmat),
                   to_chunks(xb)))
    y = ys.transpose(1, 0, 2, 3).reshape(B, n_chunks * chunk, di)[:, :S]
    return y, h_last


def mamba_apply(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                cache: Optional[dict] = None, mode: str = "train",
                flags: Optional[dict] = None):
    """Mamba-1 selective SSM. cache = {"conv": (B,K-1,di), "h": (B,di,n)}.

    flags["mamba_fused"] (default True) fuses the C-projection into the
    chunk loop (see _selective_scan_fused); False keeps the naive
    materialized path (the paper-faithful §Perf baseline).
    """
    flags = flags or {}
    B, S, _ = x.shape
    di, n, dr = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xb, z = jnp.split(xz, 2, axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    xb, conv_state = causal_conv1d(xb, p["conv_w"], p["conv_b"], conv_state)
    xb = jax.nn.silu(xb)
    proj = jnp.einsum("bse,ef->bsf", xb, p["x_proj"])
    dt_raw = proj[..., :dr]
    Bmat = proj[..., dr:dr + n].astype(jnp.float32)          # (B,S,n)
    Cmat = proj[..., dr + n:].astype(jnp.float32)
    dt = jax.nn.softplus(jnp.einsum("bsf,fe->bse", dt_raw, p["dt_proj"])
                         + p["dt_bias"]).astype(jnp.float32)  # (B,S,di)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))              # (di,n)
    h0 = (cache["h"] if cache is not None
          else jnp.zeros((B, di, n), jnp.float32))
    if mode == "decode" and S == 1:
        a_1 = jnp.exp(dt[:, 0, :, None] * A)
        b_1 = (dt[:, 0, :, None] * Bmat[:, 0, None, :]
               * xb.astype(jnp.float32)[:, 0, :, None])
        h_last = a_1 * h0 + b_1
        y = jnp.einsum("bdn,bn->bd", h_last, Cmat[:, 0])[:, None]
    elif flags.get("mamba_kernel", False):
        # Pallas fused selective-scan kernel (kernels/selective_scan.py):
        # HBM traffic = inputs + outputs only (TPU target; interpret on CPU)
        from ..kernels import ops as kops
        y, h_last = kops.selective_scan(dt, xb.astype(jnp.float32), Bmat,
                                        Cmat, A, h0)
    elif flags.get("mamba_fused", True):
        y, h_last = _selective_scan_fused(dt, Bmat, xb, A, Cmat, h0,
                                          chunk=flags.get("scan_chunk", 128))
    else:
        a_seq = jnp.exp(dt[..., None] * A)                    # (B,S,di,n)
        b_seq = (dt[..., None] * Bmat[:, :, None, :]
                 * xb.astype(jnp.float32)[..., None])
        h_all, h_last = linear_scan_chunked(
            a_seq, b_seq, h0, chunk=flags.get("scan_chunk", 128))
        y = jnp.einsum("bsdn,bsn->bsd", h_all, Cmat)
    y = y.astype(x.dtype) + p["d_skip"] * xb
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    new_cache = {"conv": conv_state, "h": h_last}
    return out, new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    return {"conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.d_inner), dtype),
            "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32)}


# ================================================================== RG-LRU

def init_rglru(pb, p, a, cfg: ModelConfig):
    d, w, K = cfg.d_model, cfg.lru_dim, cfg.conv1d_width
    pb.param(p, a, "w_branch", (d, w), ("embed", "lru"))
    pb.param(p, a, "w_gate_branch", (d, w), ("embed", "lru"))
    pb.param(p, a, "conv_w", (K, w), ("conv", "lru"), scale=0.5)
    pb.param(p, a, "conv_b", (w,), ("lru",), init="zeros")
    pb.param(p, a, "w_a", (w, w), ("lru", "lru"), scale=0.02)
    pb.param(p, a, "b_a", (w,), ("lru",), init="zeros")
    pb.param(p, a, "w_i", (w, w), ("lru", "lru"), scale=0.02)
    pb.param(p, a, "b_i", (w,), ("lru",), init="zeros")
    pb.param(p, a, "lambda_p", (w,), ("lru",), init="lru_a")
    pb.param(p, a, "out_proj", (w, d), ("lru", "embed"))


def rglru_apply(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                cache: Optional[dict] = None, mode: str = "train",
                flags: Optional[dict] = None):
    """Griffin recurrent block: conv1d + RG-LRU gated diagonal recurrence.

    cache = {"conv": (B,K-1,w), "h": (B,w)}.
    """
    flags = flags or {}
    B, S, _ = x.shape
    xb = jnp.einsum("bsd,dw->bsw", x, p["w_branch"])
    gate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_gate_branch"]))
    conv_state = cache["conv"] if cache is not None else None
    xb, conv_state = causal_conv1d(xb, p["conv_w"], p["conv_b"], conv_state)
    r = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xb, p["w_a"]) + p["b_a"])
    i = jax.nn.sigmoid(jnp.einsum("bsw,wv->bsv", xb, p["w_i"]) + p["b_i"])
    c = 8.0
    log_a = (-c * jax.nn.softplus(p["lambda_p"].astype(jnp.float32))
             * r.astype(jnp.float32))
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))
    b = mult * (i * xb).astype(jnp.float32)
    h0 = cache["h"] if cache is not None else jnp.zeros((B, cfg.lru_dim),
                                                        jnp.float32)
    if mode == "decode" and S == 1:
        h_last = a[:, 0] * h0 + b[:, 0]
        h_all = h_last[:, None]
    else:
        h_all, h_last = linear_scan_chunked(a, b, h0,
                                            chunk=flags.get("scan_chunk", 256))
    y = (h_all.astype(x.dtype) * gate)
    out = jnp.einsum("bsw,wd->bsd", y, p["out_proj"])
    return out, {"conv": conv_state, "h": h_last}


def init_rglru_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    return {"conv": jnp.zeros((batch, cfg.conv1d_width - 1, cfg.lru_dim), dtype),
            "h": jnp.zeros((batch, cfg.lru_dim), jnp.float32)}
