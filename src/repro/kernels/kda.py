"""Kimi Delta Attention (KDA) in the chunked form: the delta rule with a
decay per key channel, over packed rows whose state resets at every
document.

Per head, with state S (d_k, d_v) and per token t a key k_t, query q_t,
value v_t, write strength beta_t and log-decays log a_t (d_k,):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with S_{t-1} = 0 where t starts a segment (a document piece of the row,
``seg`` changes from t-1 to t; padding is a segment like any other).
q and k arrive unnormalised and are L2-normalised here, q also scaled by
``scale`` (Kimi-Linear's ``use_qk_l2norm_in_kernel``).

Chunked form (chunks of ``CHUNK`` tokens; Yang et al., "Gated Delta
Networks", and the Kimi Linear report, arXiv:2510.26692): within a chunk
g_t is the cumulative log-decay from the chunk's start through t
(non-increasing per channel), and for a state S_0 entering the chunk

    A_ti = beta_t sum_c k_tc k_ic exp(g_tc - g_ic)     (i < t, same segment)
    T    = (I + A)^-1                                  (unit lower triangular)
    W    = T diag(beta) (k * exp(g) * m_in),  U = T diag(beta) v
    V'   = U - W S_0                                   (the corrected values)
    P_ti = sum_c q_tc k_ic exp(g_tc - g_ic)            (i <= t, same segment)
    O    = (q * exp(g) * m_in) S_0 + P V'
    S_C  = c exp(g_C) * S_0 + (k * exp(g_C - g) * l_out)^T V'

where m_in marks the tokens of the segment the incoming state belongs to
(no segment starts between the chunk's start and them), l_out the tokens
of the chunk's last segment, and c whether that last segment began in an
earlier chunk.  So the intra-chunk matrices pair only tokens of one
segment, the incoming state reaches only its own segment's tokens, and the
outgoing state keeps the last segment alone, plus the decayed incoming
state only where that segment carries on from an earlier chunk.

Exactness.  exp(g_tc - g_ic) <= 1 for i <= t, but the factorised
exp(g_t) exp(-g_i) over- or underflows inside a chunk at strong decay.  A
and P are therefore built in sub-chunks of ``SUB`` tokens: a pair in two
different sub-chunks is factorised about g_r, r the last token before the
row's sub-chunk (g_t <= g_r <= g_i, so both factors are at most 1 and one
underflows only where the exact term is below float32's range); a pair in
one sub-chunk takes exp(g_t - g_i) itself, one key column at a time.
Nothing is clamped.  T inverts the sub-chunks' diagonal blocks as the
finite product (I - A)(I + A^2)(I + A^4)(I + A^8) (A is nilpotent there),
then substitutes block by block across them.

Where the work runs.  Two Pallas kernels under one ``jax.custom_vjp``
hold every step of the chunked form, one (row, head) a grid step and the
chunks along a sequential grid axis: ``kda_fwd`` normalises q and k,
accumulates the decays, builds A, P, T, W and U, and carries S across the
chunks, writing O (and, for the backward, the state entering every
chunk); ``kda_bwd`` walks
the chunks backwards carrying dL/dS, rebuilds each chunk's terms from the
inputs and its entering state, and writes the gradients of q, k, v, the
log-decays and beta.  Inputs arrive in bf16 (q, k, v) and float32 (the
log-decays and beta); every intermediate and the state are float32, and
every product runs at float32 precision (``HIGHEST``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens per chunk of the chunked form
CHUNK = 64
#: tokens per sub-chunk of the intra-chunk matrices
SUB = 16
#: L2-norm epsilon of q and k (FLA's ``l2norm``)
L2_EPS = 1e-6

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=_F32)


def _dot_nt(a, b):
    """a @ b^T."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _dot_tn(a, b):
    """a^T @ b."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _column(row):
    """(1, n) -> (n, 1)."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), row, 0.0),
                   axis=1, keepdims=True)


def _row(col):
    """(n, 1) -> (1, n)."""
    n = col.shape[0]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), col, 0.0),
                   axis=0, keepdims=True)


# ============================================================ segment masks
def segment_starts(seg: jax.Array) -> jax.Array:
    """(B, S) bool: token t starts a segment (t = 0, or seg changes)."""
    first = jnp.ones_like(seg[:, :1], dtype=bool)
    return jnp.concatenate([first, seg[:, 1:] != seg[:, :-1]], axis=1)


def _chunk_info(seg: jax.Array, S: int):
    """What the kernels read of the segments, from (B, S_pad) segment ids
    (S_pad % CHUNK == 0): (B, n, 4, CHUNK) float32 rows per chunk: the
    token's segment number, m_in, l_out, and cont repeated (module doc).
    And the resets those masks make over the first ``S`` tokens of the
    rows: token pairs (t-1, t) of one chunk that the masks keep apart,
    and chunks whose first token the incoming state does not reach."""
    B, Sp = seg.shape
    C, n = CHUNK, Sp // CHUNK
    sid = jnp.cumsum(segment_starts(seg).astype(jnp.int32), axis=1)
    prev = jnp.concatenate([jnp.zeros_like(sid[:, :1]), sid[:, :-1]], axis=1)
    sid = sid.reshape(B, n, C)
    prev = prev.reshape(B, n, C)[:, :, :1]       # the token before the chunk
    m_in = sid == prev
    l_out = sid == sid[..., -1:]
    cont = jnp.broadcast_to(m_in[..., -1:], m_in.shape)
    info = jnp.stack([sid.astype(_F32), m_in.astype(_F32), l_out.astype(_F32),
                      cont.astype(_F32)], axis=2)
    apart = jnp.concatenate([~m_in[:, :, :1], sid[..., 1:] != sid[..., :-1]],
                            axis=2).reshape(B, Sp)
    resets = jnp.sum(apart[:, 1:S])              # token 0 starts from zero
    return info, resets


# ===================================================== intra-chunk terms
def _intra(kn, qs, g):
    """M_ti = sum_c a_tc k_ic exp(g_tc - g_ic) over the chunk's pairs with
    i <= t, for a = kn (``mk``) and a = qs (``mq``): (C, K) -> (C, C),
    exact at any decay (module docstring)."""
    C, K = kn.shape
    t = _iota((C, 1), 0)
    tl = _iota((SUB, 1), 0)
    col = _iota((1, C), 1)
    rows_k, rows_q = [], []
    for j in range(C // SUB):
        lo = slice(j * SUB, (j + 1) * SUB)
        if j:
            gr = g[j * SUB - 1:j * SUB]
            right = jnp.where(t < j * SUB, kn * jnp.exp(gr - g), 0.0)
            dec = jnp.exp(g[lo] - gr)
            mk, mq = _dot_nt(kn[lo] * dec, right), _dot_nt(qs[lo] * dec, right)
        else:
            mk = mq = jnp.zeros((SUB, C), _F32)
        for i in range(SUB):
            ii = j * SUB + i
            e = jnp.exp(jnp.where(tl >= i, g[lo] - g[ii:ii + 1], -jnp.inf))
            u = kn[ii:ii + 1] * e
            at = col == ii
            mk = mk + jnp.where(at, jnp.sum(kn[lo] * u, axis=1, keepdims=True), 0.0)
            mq = mq + jnp.where(at, jnp.sum(qs[lo] * u, axis=1, keepdims=True), 0.0)
        rows_k.append(mk)
        rows_q.append(mq)
    return jnp.concatenate(rows_k, axis=0), jnp.concatenate(rows_q, axis=0)


def _intra_bwd(dmk, dmq, kn, qs, g):
    """The gradients of ``_intra``'s two products given theirs (zero off
    their masks): da_k = sum_i dmk_ti k_i e_ti and da_q likewise (the rows'
    factors), db = sum_t (dmk_ti k_t + dmq_ti q_t) e_ti (the key's), with
    e_ti = exp(g_t - g_i), factorised as in ``_intra``."""
    C, K = kn.shape
    t = _iota((C, 1), 0)
    tl = _iota((SUB, 1), 0)
    col = _iota((1, C), 1)
    nb = C // SUB
    out_k, out_q, out_b = [], [], []
    for j in range(nb):
        lo = slice(j * SUB, (j + 1) * SUB)
        if j:                                   # keys of earlier sub-chunks
            gr = g[j * SUB - 1:j * SUB]
            right = jnp.where(t < j * SUB, kn * jnp.exp(gr - g), 0.0)
            dec = jnp.exp(g[lo] - gr)
            ak, aq = _dot(dmk[lo], right) * dec, _dot(dmq[lo], right) * dec
        else:
            ak = aq = jnp.zeros((SUB, K), _F32)
        if j < nb - 1:                          # rows of later sub-chunks
            r = (j + 1) * SUB - 1
            gr = g[r:r + 1]
            dec = jnp.where(t > r, jnp.exp(g - gr), 0.0)
            b = (_dot_tn(dmk[:, lo], kn * dec) + _dot_tn(dmq[:, lo], qs * dec)
                 ) * jnp.exp(gr - g[lo])
        else:
            b = jnp.zeros((SUB, K), _F32)
        for i in range(SUB):
            ii = j * SUB + i
            e = jnp.exp(jnp.where(tl >= i, g[lo] - g[ii:ii + 1], -jnp.inf))
            at = col == ii
            ck = jnp.sum(jnp.where(at, dmk[lo], 0.0), axis=1, keepdims=True) * e
            cq = jnp.sum(jnp.where(at, dmq[lo], 0.0), axis=1, keepdims=True) * e
            ak = ak + ck * kn[ii:ii + 1]
            aq = aq + cq * kn[ii:ii + 1]
            b = b + jnp.where(tl == i, jnp.sum(ck * kn[lo] + cq * qs[lo], axis=0,
                                               keepdims=True), 0.0)
        out_k.append(ak)
        out_q.append(aq)
        out_b.append(b)
    cat = lambda xs: jnp.concatenate(xs, axis=0)
    return cat(out_k), cat(out_q), cat(out_b)


def _unit_lower_inverse(a):
    """(I + A)^-1 for strictly lower triangular A (C, C): the sub-chunks'
    diagonal blocks D^-1 by a finite product, then block forward
    substitution, T_j = D_j^-1 (I - A T) over the rows of sub-chunk j (T
    holding the earlier sub-chunks' rows, so A's own block meets zeros)."""
    C = a.shape[0]
    r, c = _iota((C, C), 0), _iota((C, C), 1)
    rb, cb = r // SUB, c // SUB
    eye = jnp.where(r == c, 1.0, 0.0)
    p = -jnp.where(rb == cb, a, 0.0)
    d = eye + p
    for _ in range(SUB.bit_length() - 2):       # p^2, p^4, p^8 for SUB = 16
        p = _dot(p, p)
        d = _dot(d, eye + p)
    t = jnp.where(rb == 0, d, 0.0)
    for j in range(1, C // SUB):
        t = t + _dot(d, jnp.where(rb == j, eye - _dot(a, t), 0.0))
    return t


def _terms(q, k, v, la, beta_row, info, scale):
    """A chunk's terms of the chunked form, from its inputs: q, k (C, K),
    v (C, V), la (C, K) log-decays, beta_row (1, C), and the rows of
    ``_chunk_info`` (four (1, C))."""
    C = k.shape[0]
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    rq = jax.lax.rsqrt(jnp.sum(q * q, axis=1, keepdims=True) + L2_EPS)
    rk = jax.lax.rsqrt(jnp.sum(k * k, axis=1, keepdims=True) + L2_EPS)
    qn, kn = q * rq, k * rk
    qs = qn * scale
    ti, ii = _iota((C, C), 0), _iota((C, C), 1)
    incl_tri = jnp.where(ii <= ti, 1.0, 0.0)
    g = _dot(incl_tri, la)
    sid_r, m_in, l_out, cont = info
    same = _column(sid_r) == sid_r
    strict, incl = same & (ii < ti), same & (ii <= ti)
    beta = _column(beta_row)
    m_in, l_out = _column(m_in), _column(l_out)
    cont = jnp.max(cont, axis=1, keepdims=True)
    mk, mq = _intra(kn, qs, g)
    a = jnp.where(strict, beta * mk, 0.0)
    p = jnp.where(incl, mq, 0.0)
    tinv = _unit_lower_inverse(a)
    eg = jnp.exp(g)
    x = beta * kn * eg * m_in
    y = beta * v
    gc = g[C - 1:C]
    dec = jnp.exp(gc - g)
    return dict(q=q, k=k, v=v, rq=rq, rk=rk, qn=qn, kn=kn, qs=qs, g=g,
                incl_tri=incl_tri, strict=strict, incl=incl, beta=beta,
                m_in=m_in, l_out=l_out, mk=mk, p=p, tinv=tinv, eg=eg, x=x, y=y,
                w=_dot(tinv, x), u=_dot(tinv, y), qd=qs * eg * m_in, dec=dec,
                kd=kn * dec * l_out, gam=jnp.exp(gc) * cont)


# ================================================================ kernels
def _info_rows(info_ref):
    return tuple(info_ref[0, 0, r:r + 1, :] for r in range(4))


def _fwd_kernel(q_ref, k_ref, v_ref, la_ref, b_ref, info_ref, o_ref, *rest,
                scale):
    *hs_ref, s_scr = rest                       # hs_ref where states are kept

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    z = _terms(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], la_ref[0, 0],
               b_ref[0, 0, 0], _info_rows(info_ref), scale)
    s = s_scr[...]
    if hs_ref:
        hs_ref[0][0, 0, 0] = s
    vn = z["u"] - _dot(z["w"], s)
    o_ref[0, 0] = _dot(z["qd"], s) + _dot(z["p"], vn)
    s_scr[...] = _column(z["gam"]) * s + _dot_tn(z["kd"], vn)


def _bwd_kernel(q_ref, k_ref, v_ref, la_ref, b_ref, info_ref, hs_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dla_ref, db_ref, ds_scr, *, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    C = q_ref.shape[2]
    z = _terms(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], la_ref[0, 0],
               b_ref[0, 0, 0], _info_rows(info_ref), scale)
    s, ds, do = hs_ref[0, 0, 0], ds_scr[...], do_ref[0, 0]
    w, kd, qd, tinv, beta = z["w"], z["kd"], z["qd"], z["tinv"], z["beta"]
    vn = z["u"] - _dot(w, s)
    # the state's path
    dvn = _dot_tn(z["p"], do) + _dot(kd, ds)
    dp = jnp.where(z["incl"], _dot_nt(do, vn), 0.0)
    dqd = _dot_nt(do, s)
    dkd = _dot_nt(vn, ds)
    dgam = _row(jnp.sum(s * ds, axis=1, keepdims=True))
    dw = -_dot_nt(dvn, s)
    ds_scr[...] = _dot_tn(qd, do) + _column(z["gam"]) * ds - _dot_tn(w, dvn)
    # W = T x, U = T y, T = (I + A)^-1
    dt = _dot_nt(dw, z["x"]) + _dot_nt(dvn, z["y"])
    dx = _dot_tn(tinv, dw)
    dy = _dot_tn(tinv, dvn)
    da = jnp.where(z["strict"], -_dot_tn(tinv, _dot_nt(dt, tinv)), 0.0)
    kn, qs, eg, m_in, l_out, dec = (z["kn"], z["qs"], z["eg"], z["m_in"],
                                    z["l_out"], z["dec"])
    dv = beta * dy
    dbeta = (jnp.sum(dy * z["v"], axis=1, keepdims=True)
             + jnp.sum(dx * kn * eg * m_in, axis=1, keepdims=True)
             + jnp.sum(da * z["mk"], axis=1, keepdims=True))
    dkn = dx * beta * eg * m_in + dkd * dec * l_out
    dqs = dqd * eg * m_in
    ddec = dkd * kn * l_out
    dg = (dx * beta * kn + dqd * qs) * m_in * eg - ddec * dec
    dgc = jnp.sum(ddec * dec, axis=0, keepdims=True) + dgam * z["gam"]
    dg = dg + jnp.where(_iota((C, 1), 0) == C - 1, dgc, 0.0)
    # A = beta mk (strict), P = mq (inclusive)
    ak, aq, db = _intra_bwd(beta * da, dp, kn, qs, z["g"])
    dkn = dkn + ak + db
    dqs = dqs + aq
    dg = dg + kn * ak + qs * aq - kn * db
    dla_ref[0, 0] = _dot_tn(z["incl_tri"], dg)
    dqn = dqs * scale
    qn, rk = z["qn"], z["rk"]
    dq_ref[0, 0] = (z["rq"] * (dqn - qn * jnp.sum(qn * dqn, axis=1, keepdims=True))
                    ).astype(dq_ref.dtype)
    dk_ref[0, 0] = (rk * (dkn - kn * jnp.sum(kn * dkn, axis=1, keepdims=True))
                    ).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    db_ref[0, 0, 0] = _row(dbeta)


def _specs(n: int, C: int, reverse: bool):
    """Block specs of a per-token (C, d) operand, a per-chunk (r, d) one
    of each head, and the per-chunk rows of each row's segments; the
    chunks walked forwards or backwards."""
    j = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    tok = lambda d: pl.BlockSpec((1, 1, C, d), lambda b, h, c: (b, h, j(c), 0))
    mat = lambda r, d: pl.BlockSpec((1, 1, 1, r, d),
                                    lambda b, h, c: (b, h, j(c), 0, 0))
    info = pl.BlockSpec((1, 1, 4, C), lambda b, h, c: (b, j(c), 0, 0))
    return tok, mat, info


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kda_fwd(q, k, v, la, beta, info, scale, interpret, keep_states):
    """O, and where ``keep_states`` the state entering every chunk."""
    B, H, S, K = q.shape
    V = v.shape[-1]
    n = S // CHUNK
    tok, mat, inf = _specs(n, CHUNK, reverse=False)
    states = [(mat(K, V), jax.ShapeDtypeStruct((B, H, n, K, V), _F32))] * keep_states
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(B, H, n),
        in_specs=[tok(K), tok(K), tok(V), tok(K), mat(1, CHUNK), inf],
        out_specs=[tok(V)] + [spec for spec, _ in states],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, V), _F32)]
        + [shape for _, shape in states],
        scratch_shapes=[pltpu.VMEM((K, V), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, la, beta, info)


def _kda_bwd(res, do, scale, interpret):
    q, k, v, la, beta, info, hs = res
    B, H, S, K = q.shape
    V = v.shape[-1]
    n = S // CHUNK
    tok, mat, inf = _specs(n, CHUNK, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(B, H, n),
        in_specs=[tok(K), tok(K), tok(V), tok(K), mat(1, CHUNK), inf,
                  mat(K, V), tok(V)],
        out_specs=[tok(K), tok(K), tok(V), tok(K), mat(1, CHUNK)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(la.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((K, V), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, la, beta, info, hs, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunked(q, k, v, la, beta, info, scale, interpret):
    return _kda_fwd(q, k, v, la, beta, info, scale, interpret, False)[0]


def _chunked_fwd(q, k, v, la, beta, info, scale, interpret):
    o, hs = _kda_fwd(q, k, v, la, beta, info, scale, interpret, True)
    return o, (q, k, v, la, beta, info, hs)


def _chunked_bwd(scale, interpret, res, do):
    grads = _kda_bwd(res, do.astype(_F32), scale, interpret)
    return (*grads[:4], grads[4].astype(res[4].dtype), jnp.zeros_like(res[5]))


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


# ================================================================== entry
def _pad_to_chunk(x: jax.Array, axis: int, fill) -> jax.Array:
    pad = -x.shape[axis] % CHUNK
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
        beta: jax.Array, seg: jax.Array, *, scale: float,
        interpret: bool = False):
    """Heads first: q, k (B, H, S, K), v (B, H, S, V), log_a (B, H, S, K)
    (<= 0), beta (B, H, S), seg (B, S) -> (o (B, H, S, V) float32, the
    state resets the kernels' segment masks make), the chunked KDA over
    rows whose state starts at zero and resets at every segment start.  A
    row is padded to whole chunks with a segment of its own."""
    B, H, S, _ = q.shape
    pad = lambda x: _pad_to_chunk(x, 2, 0)
    seg = _pad_to_chunk(seg.astype(jnp.int32), 1, jnp.iinfo(jnp.int32).min)
    info, resets = _chunk_info(seg, S)
    n = seg.shape[1] // CHUNK
    b = pad(beta.astype(_F32)).reshape(B, H, n, 1, CHUNK)
    o = _chunked(pad(q), pad(k), pad(v), pad(log_a.astype(_F32)), b, info,
                 scale, interpret)
    return o[:, :, :S], resets


__all__ = ["CHUNK", "SUB", "kda", "segment_starts"]
