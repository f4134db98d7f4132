"""State-space / attention hybrid LM with a dense feed-forward behind
every mixer (the Granite 4.0-H block, as ``granite-4.0-h-micro``
publishes it under ``model_type: granitemoehybrid`` with
``num_local_experts`` 0).

With ``e`` = ``embedding_multiplier``, ``m`` = ``residual_multiplier``,
``a`` = ``attention_multiplier``, ``c`` = ``logits_scaling`` and ``E``
the one ``[vocab, hidden]`` table (``tie_word_embeddings``)::

    h = e * E[ids]
    for layer i:  h = h + m * mixer_i(norm(h));  h = h + m * swiglu(norm(h))
    logits = (norm(h) @ E^T) / c

``layer_types[i]`` names the mixer: ``mamba`` a Mamba-2 mixer
(``Mamba2Mixer``: one input projection into a gate ``z``, the convolved
channels ``x | B | C`` and a step ``dt`` a head; a causal depthwise
convolution with bias and a silu; the selective scan with ``B`` and
``C`` in ``mamba_n_groups`` groups; ``norm(y * silu(z))``, the gate
first; the output projection), ``attention`` grouped-query causal
attention with no position term (``position_embedding_type: nope``) and
the softmax scale ``a`` in the place of ``head_dim ** -0.5``
(``BandedAttention``). The feed-forward is ``DenseMLP`` of
``shared_intermediate_size``. The file lists layers of
:mod:`elephas_tpu.models.lm_blocks` and
:mod:`elephas_tpu.models.lm_mixers` and defines none.

``fit`` only: the state cache and the one-token step that serving a
recurrent layer needs are not here, and a sequence made of several
documents resets no state at their boundaries.
"""

from __future__ import annotations

from functools import partial

LAYER_TYPES = {"mamba": "mamba", "attention": "attn"}


def granite_hybrid_lm(
    vocab_size: int = 1024,
    maxlen: int = 128,
    hidden_size: int = 64,
    layer_types=("mamba", "mamba", "attention", "mamba"),
    num_hidden_layers: int | None = None,
    mamba_n_heads: int = 8,
    mamba_d_head: int = 8,
    mamba_d_state: int = 16,
    mamba_n_groups: int = 1,
    mamba_d_conv: int = 4,
    mamba_chunk_size: int = 256,
    time_step_min: float = 0.001,
    time_step_max: float = 0.1,
    time_step_floor: float = 1e-4,
    num_attention_heads: int = 4,
    num_key_value_heads: int = 2,
    head_dim: int | None = None,
    shared_intermediate_size: int = 256,
    attention_multiplier: float | None = None,
    embedding_multiplier: float | None = None,
    residual_multiplier: float | None = None,
    logits_scaling: float | None = None,
    rms_norm_eps: float = 1e-5,
    init_std: float = 0.02,
    lr: float = 0.01,
    momentum: float = 0.9,
    seed: int = 0,
    dtype_policy: str | None = None,
    remat: bool = False,
):
    """Decoder-only hybrid LM whose layer ``i`` is the mixer that
    ``layer_types[i]`` names (``mamba``: a Mamba-2 mixer; ``attention``:
    grouped-query causal attention with no position term, its scores
    times ``attention_multiplier``) and then a dense SwiGLU of
    ``shared_intermediate_size``, each under its own pre-norm and added
    to the stream times ``residual_multiplier``; the embedding times
    ``embedding_multiplier``, the logits over ``logits_scaling``, the
    head always the embedding's table (``tie_word_embeddings``: no
    argument, since no untied variant is built or tested). The argument
    names are the published config's; a multiplier left None is left
    out of the program (``attention_multiplier`` None: ``head_dim **
    -0.5``). ``num_hidden_layers`` reads that many leading entries of
    ``layer_types`` (all of them when None); ``head_dim`` None is
    ``hidden_size / num_attention_heads``.

    ``remat``: every mixer and every feed-forward keeps its input for
    the backward pass and is computed again there (an attention layer
    also what the flash kernels read and give: q, k and v, the result
    and its log-sum-exp). Compiled with SGD (``lr``, ``momentum``) and
    next-token cross-entropy over float32 logits."""
    n = len(layer_types) if num_hidden_layers is None else num_hidden_layers
    kinds = tuple(layer_types[:n])
    if len(kinds) < n or set(kinds) - set(LAYER_TYPES):
        raise ValueError(
            f"{n} layers of layer_types {list(layer_types)!r}: its "
            f"entries are {sorted(LAYER_TYPES)}"
        )
    head_dim = hidden_size // num_attention_heads if head_dim is None else (
        head_dim)
    from elephas_tpu.models import lm_blocks, lm_mixers

    def mixer(i):
        name = f"layer{i}_{LAYER_TYPES[kinds[i]]}"
        if kinds[i] == "mamba":
            return partial(
                lm_mixers.Mamba2Mixer, mamba_n_heads, mamba_d_head,
                mamba_d_state, mamba_n_groups, mamba_d_conv,
                mamba_chunk_size, rms_norm_eps, time_step_min,
                time_step_max, time_step_floor, init_std, remat=remat,
                name=name)
        return partial(
            lm_mixers.BandedAttention, num_attention_heads,
            num_key_value_heads, head_dim, None, False, init_std=init_std,
            scale=attention_multiplier, remat=remat, name=name)

    return lm_blocks.decoder_lm(
        "granite_hybrid_lm",
        [[lm_blocks.SubLayer("input_norm", mixer(i)),
          lm_blocks.SubLayer("post_norm", partial(
              lm_blocks.DenseMLP, shared_intermediate_size, init_std,
              remat=remat, name=f"layer{i}_mlp"))]
         for i in range(n)],
        partial(lm_blocks.RMSNorm, rms_norm_eps),
        vocab_size=vocab_size, maxlen=maxlen, hidden_size=hidden_size,
        init_std=init_std, lr=lr, momentum=momentum, seed=seed,
        dtype_policy=dtype_policy,
        embedding_multiplier=embedding_multiplier,
        residual_multiplier=residual_multiplier,
        logits_scaling=logits_scaling,
        tie_word_embeddings=True)
