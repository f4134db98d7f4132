"""State-space duality (SSD) — the selective scan of Mamba-2 layers.

Per head, with a state ``S`` of ``[P, N]`` that starts at zero, token by
token::

    S = exp(dt_t * A) * S + dt_t * x_t B_t^T    # A < 0: a scalar decay a head
    y_t = S C_t + D * x_t

``x_t [P]`` is the head's input, ``B_t`` and ``C_t [N]`` are shared by
the heads of a group (head ``h`` reads group ``h // (H / G)``), ``dt_t``
is the head's step (positive, already through its softplus) and ``D``
its skip.

:func:`ssd_recurrent` is that loop as a ``lax.scan`` over tokens: the
oracle of the tests, one step a token. :func:`ssd_chunked` is the
chunked form that training runs. Inside a chunk of ``Q`` tokens the
rule is a masked product, ``y = (C B^T o L) (dt x)`` with ``L_ij =
exp(a_i - a_j)`` for ``j <= i`` and ``a`` the running sum of ``dt A``
within the chunk; a chunk's own state is ``B^T (decay dt x)``; only the
chunk-to-chunk states go through a scan of ``S / Q`` steps, whose body
is one multiply-add of the state; and what the state carried into a
chunk adds is ``exp(a_i) C_i S``. The steps, the running sums, every
``exp(difference of running sums)`` (never a quotient of two
exponentials) and the state stay float32 whatever the inputs are; the
products between blocks take their operands in the inputs' dtype and
accumulate in float32. Both are plain ``jax.numpy``, so ``jax.grad``
gives the backward pass. No product of the rule is dear (a layer's whole
rule is a twentieth of its projections' operations), so nothing here
carries a name for a caller's ``jax.checkpoint`` policy to keep: a
rematerialised mixer computes the rule again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 128


def _grouped(h: int, g: int) -> int:
    if h % g:
        raise ValueError(f"{h} heads over {g} groups of B and C")
    return h // g


def ssd_recurrent(x, dt, A, B, C, D):
    """Token-by-token recurrence. ``x``: ``[B, S, H, P]``; ``dt``:
    ``[B, S, H]``; ``A`` and ``D``: ``[H]``; ``B`` and ``C``: ``[B, S,
    G, N]``. Returns ``(y [B, S, H, P], final state [B, H, P, N])`` in
    float32."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (t.astype(f32) for t in (x, dt, A, B, C, D))
    b, _s, h, p = x.shape
    per = _grouped(h, B.shape[2])
    B, C = (jnp.repeat(t, per, axis=2) for t in (B, C))  # a head its group's
    hi = jax.lax.Precision.HIGHEST

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * A)[..., None, None] + (
            (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=hi)
        return state, y_t + D[:, None] * x_t

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    state, y = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1]), f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, A, B, C, D, chunk_size: int = DEFAULT_CHUNK):
    """Chunked SSD; same arguments and results as :func:`ssd_recurrent`,
    with ``y`` in ``x``'s dtype. ``S`` need not be a whole number of
    chunks: the tail is padded with tokens that neither write nor decay
    (``dt`` 0)."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    per = _grouped(h, g)
    dtype = x.dtype
    q = int(chunk_size)
    pad = (-s) % q
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        x, B, C = (jnp.pad(t, widths + ((0, 0),)) for t in (x, B, C))
        dt = jnp.pad(dt, widths)
    nc = (s + pad) // q

    def chunks(t, heads):  # [B, S, heads.., ...] -> [B, heads.., Nc, Q, ...]
        t = t.reshape((b, nc, q) + heads + t.shape[3:])
        return jnp.moveaxis(t, (1, 2), (1 + len(heads), 2 + len(heads)))

    x_c = chunks(x, (g, per))                    # [B, G, R, Nc, Q, P]
    dt_c = chunks(dt.astype(f32), (g, per))      # [B, G, R, Nc, Q]
    b_c, c_c = chunks(B, (g,)), chunks(C, (g,))  # [B, G, Nc, Q, N]
    log_decay = dt_c * A.astype(f32).reshape(g, per)[:, :, None, None]
    decay = jnp.cumsum(log_decay, axis=-1)       # [B, G, R, Nc, Q], <= 0

    def dot(spec, lhs, rhs):
        return jnp.einsum(spec, lhs, rhs, preferred_element_type=f32)

    # inside a chunk: (C B^T o L) (dt x), L_ij = exp(decay_i - decay_j)
    # for j <= i, else 0: never above 1
    lower_or_diag = jnp.tril(jnp.ones((q, q), bool))
    gap = decay[..., :, None] - decay[..., None, :]
    mask = jnp.where(lower_or_diag, jnp.exp(jnp.where(
        lower_or_diag, gap, 0.0)), 0.0)          # [B, G, R, Nc, Q, Q]
    cb = dot("bgnis,bgnjs->bgnij", c_c, b_c)     # a group's, shared by its heads
    scores = cb[:, :, None] * mask * dt_c[..., None, :]
    y = dot("bgrnij,bgrnjp->bgrnip", scores.astype(dtype), x_c)

    # a chunk's own state at its end: B^T (exp(decay_last - decay) dt x)
    last = decay[..., -1]                        # [B, G, R, Nc]
    x_out = (x_c.astype(f32) * (
        jnp.exp(last[..., None] - decay) * dt_c)[..., None]).astype(dtype)
    written = dot("bgnjs,bgrnjp->bgrnps", b_c, x_out)  # [B, G, R, Nc, P, N]

    def carry(state, xs):  # hands each chunk the state before it
        written_n, keep_n = xs
        return state * keep_n[..., None, None] + written_n, state

    state, before = jax.lax.scan(
        carry, jnp.zeros((b, g, per, p, n), f32),
        (jnp.moveaxis(written, 3, 0), jnp.moveaxis(jnp.exp(last), 3, 0)))
    before = jnp.moveaxis(before, 0, 3)          # [B, G, R, Nc, P, N]
    y = y + jnp.exp(decay)[..., None] * dot(
        "bgnis,bgrnps->bgrnip", c_c, before.astype(dtype))

    y = y + D.astype(f32).reshape(g, per)[:, :, None, None, None] * x_c.astype(f32)
    y = jnp.moveaxis(y, (3, 4), (1, 2)).reshape(b, nc * q, h, p)
    return y[:, :s].astype(dtype), state.reshape(b, h, p, n)
