"""Decoder-only transformer LM in Flax — the long-context flagship model.

No reference counterpart (Horovod 0.18.2 ships CNN benchmark models only,
`examples/tensorflow2_synthetic_benchmark.py:35-40`); the transformer is this
framework's vehicle for its first-class long-context story. TPU-first design:

  * bfloat16 compute / fp32 params; every matmul is MXU-shaped
    (d_model and head_dim multiples of 128/64).
  * Attention is **pluggable**: the default is the Pallas flash kernel
    (`ops/pallas_kernels.flash_attention`, jnp fallback off-TPU); sequence
    parallelism injects ring attention (`parallel/ring_attention.ring_attention`)
    so the SAME model definition trains with the sequence axis sharded over
    an ``sp`` mesh axis (`parallel/sp_training.py`).
  * ``pos_offset`` lets a sequence-sharded caller feed LOCAL token blocks
    while position embeddings stay GLOBAL (offset = shard_index * local_len).
  * Pre-LN blocks, GELU MLP (4x), learned positions, weight-tied output head —
    the standard GPT-2-ish recipe, chosen so parameter counts line up with
    public configs for benchmarking.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

AttnFn = Callable[..., Any]  # (q, k, v) -> out, all [B, T, H, Dh]


def default_attention(q, k, v):
    """Causal attention via the Pallas flash kernel (falls back to plain jnp
    attention when the kernel is gated off or shapes are ragged)."""
    from ..ops.pallas_kernels import flash_attention

    return flash_attention(q, k, v, causal=True)


def cached_attention(q, k, v, past_mask):
    """Attention for KV-cache inference (serving prefill/decode).

    ``q``: new-token queries [B, T, H, Dh]; ``k``/``v``: cached past K/V
    concatenated with the new block, [B, P+T, H, Dh]; ``past_mask``: bool
    [B, P] validity of each cached slot (False = padding in a gathered
    paged cache). New tokens attend causally within their own block and to
    every valid past slot.

    Masking is exact -inf: a padded slot's softmax weight is exactly 0.0
    and contributes exactly 0.0 to the weighted sum, so — at fixed array
    shapes — a request's output is bit-identical no matter how much
    padding or which other requests share the batch (the property
    ``serving/engine.py``'s batched-equals-sequential guarantee rests on;
    asserted by tests/test_serving.py).
    """
    b, t, _, dh = q.shape
    p = k.shape[1] - t
    scale = 1.0 / float(dh) ** 0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    new_mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]  # [T, T]
    mask = jnp.concatenate(
        [jnp.broadcast_to(past_mask[:, None, :], (b, t, p)),
         jnp.broadcast_to(new_mask[None], (b, t, t))], axis=-1)
    s = jnp.where(mask[:, None], s, -jnp.inf)
    # every row has at least its own (causal-self) slot, so the max is
    # finite and exp(-inf - m) underflows to exactly 0.0 for masked slots
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


class Block(nn.Module):
    num_heads: int
    dtype: Any
    attn_fn: AttnFn

    @nn.compact
    def __call__(self, x, kv=None):
        """``kv``: None for training/full-context forward (causal
        ``attn_fn``, returns the block output alone — the seam every
        existing caller uses unchanged), or ``(k_past, v_past, past_mask)``
        for KV-cache inference (``cached_attention`` over past + new,
        returns ``(output, (k_new, v_new))`` so the caller can extend its
        cache)."""
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        dense = partial(nn.Dense, dtype=self.dtype, param_dtype=jnp.float32,
                        kernel_init=nn.initializers.normal(0.02))
        ln = partial(nn.LayerNorm, dtype=self.dtype, param_dtype=jnp.float32)

        h = ln(name="ln_attn")(x)
        qkv = dense(3 * d_model, name="qkv")(h)
        b, t = qkv.shape[:2]
        # head-major column layout [h][3][hd]: a contiguous shard of the
        # fused kernel's output dim is then WHOLE heads, so tensor
        # parallelism (parallel/tensor.py P(None,"tp") on this kernel)
        # yields head-parallel q/k/v with no resharding — a qkv-major
        # split(3) would cut each tp shard across q/k/v boundaries
        qkv = qkv.reshape(b, t, self.num_heads, 3, head_dim)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        if kv is None:
            out = self.attn_fn(q, k, v)
            new_kv = None
        else:
            k_past, v_past, past_mask = kv
            out = cached_attention(
                q, jnp.concatenate([k_past.astype(k.dtype), k], axis=1),
                jnp.concatenate([v_past.astype(v.dtype), v], axis=1),
                past_mask)
            new_kv = (k, v)
        out = dense(d_model, name="proj")(
            out.astype(self.dtype).reshape(b, t, d_model))
        x = x + out

        h = ln(name="ln_mlp")(x)
        h = dense(4 * d_model, name="mlp_in")(h)
        h = nn.gelu(h)
        h = dense(d_model, name="mlp_out")(h)
        x = x + h
        return x if kv is None else (x, new_kv)


# the names ``ops.pallas_kernels.flash_attention`` gives its kernel's outputs
_SAVE_FLASH_OUTPUTS = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")

#: rematerialization policies for ``TransformerLM(remat=...)``, mapping mode
#: name -> (wrap_in_remat, jax.checkpoint policy). "full" keeps, per layer,
#: the block's input and the flash-attention kernel's two outputs (its bf16
#: [B,T,D] output and f32 [B*H,1,T] row log-sum-exp, named "flash_out" and
#: "flash_lse" in ``ops.pallas_kernels.flash_attention``) and recomputes
#: everything else in the block during backward: activation memory = two
#: bf16 [B,T,D] a layer. The kernel's outputs are kept because they are the
#: dearest bytes in the block to rebuild: 0.44 ms of kernel for 10.8 MB a
#: layer of GPT-2 large at batch 4 x 1024, 40 us a megabyte kept, where the
#: matmuls' outputs cost 7-10 us a megabyte (PERF.md, PR 25). "dots" saves
#: matmul outputs too and recomputes only elementwise ops (cheaper backward,
#: more memory); a Pallas call is not a dot, so it names the same two.
REMAT_POLICIES = {
    "none": (False, None),
    "full": (True, _SAVE_FLASH_OUTPUTS),
    "dots": (True, jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        _SAVE_FLASH_OUTPUTS)),
}


class TransformerLM(nn.Module):
    vocab_size: int
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None  # default: causal flash attention
    # "none" | "full" | "dots": recompute each block in backward, keeping
    # only its input and the attention kernel's outputs ("full") or those
    # and the matmul outputs ("dots") — see REMAT_POLICIES
    remat: str = "none"

    @nn.compact
    def __call__(self, tokens, pos_offset=0, return_hidden=False,
                 kv_cache=None):
        """tokens: int [B, T_local]; pos_offset: global position of column 0
        (nonzero when the sequence axis is sharded across devices, and an
        int array broadcastable against [B, T] — e.g. shape [B, 1] — when
        rows sit at different positions, as in batched KV-cache decode).

        ``return_hidden=True`` skips the weight-tied logit head and returns
        the final-LN hidden states [B, T, d_model] — pair with
        ``lm_loss_chunked`` to compute the cross entropy without ever
        materializing the [B, T, vocab] logits (the logits alone are
        batch·seq·vocab·4 bytes; at batch 32, seq 1024, vocab 32k that is
        4.3 GB of HBM the chunked path never allocates).

        ``kv_cache``: None (training / full-context forward, unchanged
        return), or ``(past_k, past_v, past_mask)`` for inference serving —
        ``past_k``/``past_v`` [num_layers, B, P, H, Dh] gathered cache
        (P may be 0 for prefill, padded slots allowed), ``past_mask`` bool
        [B, P] slot validity. Returns ``(logits_or_hidden, (new_k, new_v))``
        with ``new_k``/``new_v`` [num_layers, B, T, H, Dh], the K/V of the
        new tokens for the caller's cache (serving/engine.py writes them
        into its paged pool)."""
        attn = self.attn_fn if self.attn_fn is not None else default_attention
        emb = nn.Embed(self.vocab_size, self.d_model,
                       embedding_init=nn.initializers.normal(0.02),
                       param_dtype=jnp.float32, dtype=self.dtype,
                       name="tok_emb")
        pos_table = self.param(
            "pos_emb", nn.initializers.normal(0.02),
            (self.max_seq_len, self.d_model), jnp.float32)

        # jnp.take clips out-of-range indices, which would silently reuse the
        # last position embedding — fail loudly instead. pos_offset is traced
        # under sequence parallelism (lax.axis_index), so only statically
        # checkable pieces are validated here.
        t = tokens.shape[1]
        # Concrete values (python/numpy ints AND un-traced jax scalars) get
        # the exact offset+t bound; only genuinely traced offsets (sequence
        # parallelism's lax.axis_index) fall through to the local-length
        # check.
        try:
            pos_offset = int(pos_offset)
            concrete = True
        except (TypeError, jax.errors.ConcretizationTypeError,
                jax.errors.TracerIntegerConversionError):
            concrete = False
        if concrete:
            if pos_offset + t > self.max_seq_len:
                raise ValueError(
                    f"sequence [{pos_offset}, {pos_offset + t}) exceeds "
                    f"max_seq_len={self.max_seq_len}")
        elif t > self.max_seq_len:
            raise ValueError(
                f"local sequence length {t} exceeds "
                f"max_seq_len={self.max_seq_len}")
        pos = pos_offset + jnp.arange(t)
        x = emb(tokens) + jnp.take(pos_table, pos, axis=0).astype(self.dtype)
        if self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat={self.remat!r}; expected one of "
                             f"{sorted(REMAT_POLICIES)}")
        use_remat, policy = REMAT_POLICIES[self.remat]
        block_cls = nn.remat(Block, policy=policy) if use_remat else Block
        new_ks, new_vs = [], []
        for i in range(self.num_layers):
            block = block_cls(self.num_heads, self.dtype, attn,
                              name=f"block_{i}")
            if kv_cache is None:
                x = block(x)
            else:
                past_k, past_v, past_mask = kv_cache
                x, (nk, nv) = block(x, (past_k[i], past_v[i], past_mask))
                new_ks.append(nk)
                new_vs.append(nv)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln_f")(x)
        if return_hidden:
            out = x
        else:
            # weight-tied head: logits = x @ tok_emb.T
            out = emb.attend(x.astype(jnp.float32)).astype(jnp.float32)
        if kv_cache is None:
            return out
        return out, (jnp.stack(new_ks), jnp.stack(new_vs))


@jax.named_scope("loss")
def lm_loss(logits, targets):
    """Mean next-token cross entropy; with equal-size shards the global loss
    is the pmean of per-shard values (exact)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


@jax.named_scope("loss")
def denoise_loss(logits, clean, weights):
    """Weighted cross entropy of denoising: ``sum(weights * CE(logits,
    clean)) / weights.size``, position by position (no shift). ``weights``
    ``[B, T]`` float is what the noise schedule gives a position: nought
    where the model was shown the clean token, ``1 / t`` where it was
    masked at rate ``t``, 1 a token on average."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
    return -jnp.sum(weights * ll) / weights.size


@jax.named_scope("loss")
def lm_loss_chunked(hidden, emb_table, targets, chunk_tokens=2048,
                    unroll=1):
    """Weight-tied-head cross entropy WITHOUT materializing [B, T, vocab].

    ``hidden``: final hidden states from ``apply(..., return_hidden=True)``;
    ``emb_table``: the token embedding matrix [vocab, d_model] (fp32 param);
    ``targets``: int [B, T]. Tokens are processed ``chunk_tokens`` at a time
    under a rematerialized ``lax.scan``: the forward keeps only the scalar
    partial sums, and the backward recomputes each chunk's logits on the fly
    — peak extra HBM is O(chunk_tokens · vocab) instead of O(B·T·vocab).
    The head matmul runs in bf16 with fp32 accumulation
    (``preferred_element_type``), which is the MXU-native contraction; the
    log-softmax itself stays fp32. Equivalent to
    ``lm_loss(emb.attend(hidden), targets)`` up to bf16 rounding of the
    pre-softmax logits.
    """
    b, t, d = hidden.shape
    total = b * t
    chunk = min(chunk_tokens, total)
    # pad the flattened token stream to a chunk multiple (weight 0 rows), so
    # every (batch, seq) the full-logit path accepts works at full chunk
    # width — a divisor-only fallback can degrade to pathologically thin
    # chunks (e.g. prime token counts)
    pad = (-total) % chunk
    emb_t = emb_table.astype(jnp.bfloat16).T  # [d, vocab]
    h = hidden.astype(jnp.bfloat16).reshape(total, d)
    y = targets.reshape(total)
    w = jnp.ones((total,), jnp.float32)
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        w = jnp.pad(w, (0, pad))
    n = (total + pad) // chunk
    h, y, w = (h.reshape(n, chunk, d), y.reshape(n, chunk),
               w.reshape(n, chunk))

    @jax.checkpoint
    def body(acc, xs):
        hc, yc, wc = xs
        logits = jnp.dot(hc, emb_t, preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, yc[:, None], axis=-1)[:, 0]
        return acc + jnp.sum(ll * wc), None

    # unroll>1 replicates the body inside the loop so XLA can overlap one
    # chunk's head matmul with the next chunk's operand DMA (the loop
    # boundary is otherwise a scheduling barrier each iteration)
    total_ll, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h, y, w),
                               unroll=max(1, min(unroll, n)))
    return -total_ll / total


# compact configs for tests / dry runs / benches
TransformerLMTiny = partial(TransformerLM, num_layers=2, num_heads=2,
                            d_model=128, max_seq_len=512)
TransformerLM124M = partial(TransformerLM, num_layers=12, num_heads=12,
                            d_model=768, max_seq_len=2048)
