"""Gated delta rule — the recurrent-state mixer of Gated DeltaNet layers.

Per head, with a state ``S`` of ``[Dk, Dv]`` that starts at zero, token
by token::

    S = exp(g_t) * S                  # decay, g_t <= 0
    d = beta_t * (v_t - S^T k_t)      # what the state gets wrong about v_t
    S = S + k_t d^T                   # the delta-rule write
    o_t = S^T q_t

:func:`gated_delta_rule_recurrent` is that loop as a ``lax.scan`` over
tokens: the oracle of the tests, one step a token. :func:`gated_delta_rule`
is the chunked form that training runs: inside a chunk of ``C`` tokens
the writes are resolved at once (the UT transform: a unit lower
triangular ``C x C`` system a chunk, inverted by repeated squaring, all
chunks in parallel), and only the chunk-to-chunk state goes through a
scan of ``S / C`` steps. The state, the decays and the triangular system
stay float32 whatever the inputs are; the products between ``[C, D]``
blocks take their operands in the inputs' dtype and accumulate in
float32. Both are plain ``jax.numpy``, so ``jax.grad`` gives the
backward pass; the chunk scan's body is rematerialised
(``jax.checkpoint``), so that what the backward pass keeps a chunk is
the state alone. The chunk-parallel part's dearest result, the
triangular inverses, carries the name ``RESOLVE_NAME``, so that a
caller whose own ``jax.checkpoint`` recomputes the whole op can keep
them by policy (``models.qwen3_next.GatedDeltaNet`` does).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

DEFAULT_CHUNK = 64
# the name (``jax.ad_checkpoint.checkpoint_name``) of the chunks'
# triangular inverses, for a caller whose ``jax.checkpoint`` policy
# keeps them instead of solving again in the backward pass
RESOLVE_NAME = "gdn_resolve"
# the C x C triangular system is solved in float32 on the MXU: every
# float32 product there names its precision
_SOLVE_PRECISION = jax.lax.Precision.HIGHEST


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """Token-by-token recurrence. ``q, k``: ``[B, S, H, Dk]``; ``v``:
    ``[B, S, H, Dv]``; ``g`` (log decay) and ``beta``: ``[B, S, H]``.
    Returns ``(o [B, S, H, Dv], final state [B, H, Dk, Dv])`` in
    float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    b, _s, h, dk = q.shape
    dv = v.shape[-1]
    state = jnp.zeros((b, h, dk, dv), f32)
    hi = jax.lax.Precision.HIGHEST

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        d = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    state, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1), state


@jax.custom_vjp
def _unit_lower_inverse(lower):
    """``(I + L)^-1`` for strictly lower triangular ``L`` of ``[..., C,
    C]``: ``L`` is nilpotent, so the Neumann series ends, and it factors
    as ``(I - L)(I + L^2)(I + L^4)...`` with ``log2(C)`` squarings. The
    backward pass needs the inverse alone (``d(A^-1) = -A^-1 dA A^-1``),
    not the powers."""
    c = lower.shape[-1]
    eye = jnp.eye(c, dtype=lower.dtype)
    inverse = eye - lower
    power = lower
    span = 2
    while span < c:
        power = jnp.matmul(power, power, precision=_SOLVE_PRECISION)
        inverse = jnp.matmul(inverse, eye + power, precision=_SOLVE_PRECISION)
        span *= 2
    return inverse


def _unit_lower_inverse_fwd(lower):
    # result and residual are one named value: a policy that keeps the
    # name keeps both, and the rematerialised pass solves nothing
    inverse = checkpoint_name(_unit_lower_inverse(lower), RESOLVE_NAME)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-jnp.matmul(
        jnp.matmul(transposed, g, precision=_SOLVE_PRECISION), transposed,
        precision=_SOLVE_PRECISION,
    ),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk_size: int = DEFAULT_CHUNK):
    """Chunked gated delta rule; same arguments and results as
    :func:`gated_delta_rule_recurrent`, with ``o`` in ``v``'s dtype.
    ``S`` need not be a whole number of chunks: the tail is padded with
    tokens that neither write (``beta`` 0, ``k`` 0) nor decay (``g`` 0)."""
    f32 = jnp.float32
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    dtype = v.dtype
    c = int(chunk_size)
    pad = (-s) % c
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(t, widths + ((0, 0),)) for t in (q, k, v))
        g, beta = jnp.pad(g, widths), jnp.pad(beta, widths)
    n = (s + pad) // c

    def chunks(t):  # [B, S, H, ...] -> [B, H, N, C, ...]
        t = t.reshape((b, n, c) + t.shape[2:])
        return jnp.moveaxis(t, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    decay = jnp.cumsum(g, axis=-1)  # [B, H, N, C], <= 0
    # exp(decay_i - decay_j) for j <= i, else 0: never above 1
    lower_or_diag = jnp.tril(jnp.ones((c, c), bool))
    gap = decay[..., :, None] - decay[..., None, :]
    decay_mask = jnp.where(lower_or_diag, jnp.exp(jnp.where(
        lower_or_diag, gap, 0.0)), 0.0)
    k_beta = (k.astype(f32) * beta[..., None]).astype(dtype)
    v_beta = (v.astype(f32) * beta[..., None]).astype(dtype)

    def dot(x, y, spec):
        return jnp.einsum(spec, x, y, preferred_element_type=f32)

    # the writes of a chunk see each other: (I + L) u = beta v, with
    # L_ij = beta_i k_i.k_j exp(decay_i - decay_j) below the diagonal
    kk = dot(k_beta, k, "bhnid,bhnjd->bhnij") * decay_mask
    resolve = _unit_lower_inverse(jnp.tril(kk, -1))
    resolve_lo = resolve.astype(dtype)
    u = dot(resolve_lo, v_beta, "bhnij,bhnjd->bhnid")
    w = dot(
        resolve_lo,
        (k_beta.astype(f32) * jnp.exp(decay)[..., None]).astype(dtype),
        "bhnij,bhnjd->bhnid",
    )
    qk = dot(q, k, "bhnid,bhnjd->bhnij") * decay_mask
    q_in = (q.astype(f32) * jnp.exp(decay)[..., None]).astype(dtype)
    last = decay[..., -1]  # [B, H, N]
    k_out = (k.astype(f32)
             * jnp.exp(last[..., None] - decay)[..., None]).astype(dtype)

    state = jnp.zeros((b, h, dk, dv), f32)

    @jax.checkpoint
    def step(state, xs):
        u_n, w_n, qk_n, q_n, k_n, last_n = xs
        state_lo = state.astype(dtype)
        v_new = u_n - dot(w_n.astype(dtype), state_lo, "bhik,bhkv->bhiv")
        v_lo = v_new.astype(dtype)
        out = dot(q_n, state_lo, "bhik,bhkv->bhiv") + dot(
            qk_n.astype(dtype), v_lo, "bhij,bhjv->bhiv")
        state = state * jnp.exp(last_n)[..., None, None] + dot(
            k_n, v_lo, "bhik,bhiv->bhkv")
        return state, out.astype(dtype)

    xs = tuple(jnp.moveaxis(t, 2, 0) for t in (u, w, qk, q_in, k_out, last))
    state, out = jax.lax.scan(step, state, xs)  # out [N, B, H, C, Dv]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * c, dv)
    return jnp.moveaxis(out, 1, 2)[:, :s], state
