"""WKV6 (RWKV-6 "Finch") chunked recurrence as a Pallas TPU kernel.

Grid (b, H, n_chunks) with chunks innermost: the per-head state S ∈ R^{K×V}
lives in VMEM scratch across the sequential chunk dimension — the HBM
traffic is exactly one read of r/k/v/decay and one write of the output per
token (the recurrence state never round-trips to HBM, which is what makes
the attention-free family memory-optimal on TPU).

Math (checked against ref.py's sequential recurrence):
    a       = cumsum(log-decay) within the chunk           (<= 0)
    o_inter = (r ⊙ exp(a_prev)) · S_in
    o_intra = tril_strict[(r ⊙ exp(a_prev))(k ⊙ exp(-a))ᵀ] · v   (clipped exp)
    o_bonus = (r ⊙ u ⊙ k summed over K) · v
    S_out   = diag(exp(a_last)) S_in + (k ⊙ exp(a_last − a))ᵀ v
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CLIP = 40.0


def _wkv_kernel(r_ref, k_ref, v_ref, la_ref, u_ref, o_ref, s_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    rr = r_ref[0, 0, 0].astype(jnp.float32)          # (c, K)
    kk = k_ref[0, 0, 0].astype(jnp.float32)
    vv = v_ref[0, 0, 0].astype(jnp.float32)
    ll = la_ref[0, 0, 0].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)            # (1, K)

    a = jnp.cumsum(ll, axis=0)                    # (c, K), <= 0, decreasing
    a_prev = a - ll
    S = s_ref[...]                                # (K, V)

    o_inter = (rr * jnp.exp(a_prev)) @ S
    r_f = rr * jnp.exp(jnp.clip(a_prev, -_CLIP, _CLIP))
    k_f = kk * jnp.exp(jnp.clip(-a, -_CLIP, _CLIP))
    att = jax.lax.dot_general(r_f, k_f, (((1,), (1,)), ((), ())))  # (c, c)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(rows > cols, att, 0.0)        # strictly lower triangular
    o_intra = att @ vv
    o_bonus = jnp.sum(rr * u * kk, axis=1, keepdims=True) * vv

    o_ref[0, 0, 0] = (o_inter + o_intra + o_bonus).astype(o_ref.dtype)

    a_last = a[-1:]
    k_dec = kk * jnp.exp(a_last - a)
    s_ref[...] = S * jnp.exp(a_last).T + jax.lax.dot_general(
        k_dec, vv, (((0,), (0,)), ((), ())))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, la, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/la: (b, H, s, K) [s % chunk == 0]; u: (H, K).

    Returns out (b, H, s, K) f32."""
    b, H, s, K = r.shape
    assert s % chunk == 0
    n = s // chunk
    grid = (b, H, n)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    rs = lambda t: t.reshape(b, H, n, chunk, K)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, K), lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, K), lambda bi, hi, ci: (hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, K),
                               lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, H, n, chunk, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
    )(rs(r), rs(k), rs(v), rs(la), u)
    return out.reshape(b, H, s, K)
