"""BERT — the flagship transformer (BASELINE config 4: "BERT-base from
GluonNLP (HybridBlock -> XLA, multi-host KVStore)").

Built from this framework's gluon layers; hybridizes to one XLA program.
TPU-first: attention runs in bfloat16-friendly einsum form on the MXU;
sequence-parallel long-context uses mx.parallel.ring_attention; tensor
parallelism comes from ShardedTrainer rules (bert_sharding_rules below).
"""

import math

from ..gluon.block import (HybridBlock, current_trace,
                           trace_on_one_device)
from ..gluon import nn

__all__ = ["BERTModel", "BERTEncoder", "TransformerEncoderLayer",
           "MultiHeadAttention", "bert_base", "bert_large",
           "bert_sharding_rules", "BERTForPretrain"]


class MultiHeadAttention(HybridBlock):
    def __init__(self, units, num_heads, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        with self.name_scope():
            self.query = nn.Dense(units, flatten=False, prefix="query_")
            self.key = nn.Dense(units, flatten=False, prefix="key_")
            self.value = nn.Dense(units, flatten=False, prefix="value_")
            self.proj = nn.Dense(units, flatten=False, prefix="proj_")
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None):
        B = x.shape[0]
        T = x.shape[1]
        D = self._units // self._num_heads
        out = self._attend(F, self.query(x), self.key(x), self.value(x),
                           mask, B, T, D)
        return self.proj(out)

    def _split_heads(self, F, x, B, T, D):
        x = F.reshape(x, shape=(B, T, self._num_heads, D))
        return F.transpose(x, axes=(0, 2, 1, 3))            # (B,H,T,D)

    def _merge_heads(self, F, x, B, T):
        x = F.transpose(x, axes=(0, 2, 1, 3))
        return F.reshape(x, shape=(B, T, self._units))

    def _attend(self, F, q, k, v, mask, B, T, D):
        """q, k, v: (B, T, H*D) as the projections leave them; so is the
        result. The path is chosen BEFORE any reshape: only the ring and
        einsum paths (and the flash kernel's tiled form, inside
        ``flash_attention_bthd``) carry heads outermost."""
        # Sequence-parallel fast path (VERDICT r4 #3): when tracing under a
        # ShardedTrainer whose mesh carries sp>1, attention runs as RING
        # attention over the sp axis — flash per KV shard with online-
        # softmax stats across ppermute hops — instead of letting GSPMD
        # all-gather the sequence axis. SURVEY §5's "sequence-axis sharding
        # + ring/flash" as ONE capability of the model surface.
        import os as _os
        ctx = current_trace()
        mesh = getattr(ctx, "mesh_ctx", None) if ctx is not None else None
        if (mesh is not None and "sp" in mesh.axis_names
                and dict(mesh.shape)["sp"] > 1
                and mask is None and self.dropout._rate == 0
                and _os.environ.get("MXTPU_DISABLE_RING", "0") != "1"
                and T % dict(mesh.shape)["sp"] == 0):
            q, k, v = (self._split_heads(F, a, B, T, D) for a in (q, k, v))
            return self._merge_heads(
                F, self._ring_attend(q, k, v, mesh, T, D), B, T)
        # Pallas flash-attention fast path when on TPU inside a trace with
        # no attention-dropout; einsum otherwise. Valid-length masks ride
        # the kernel's kv-mask path (r2). In the T=512 step (B=32, 12 heads
        # of 64) the one-tile launches take 0.39 ms forward and 0.71 ms
        # backward a layer, where the tiled ones took 0.92 and 1.25 with
        # 1.0 ms of transposes and row statistics around them (PERF.md
        # section 5, cell 3). Under 512 the einsum path with the fused
        # softmax runs (section 5, cell 1); where the two cross is not
        # measured, so the threshold stays env-tunable (MXTPU_FLASH_MIN_T,
        # default 512; ROADMAP A3 settles it); the T % 128 tiling contract
        # is NOT tunable. MXTPU_DISABLE_FLASH=1 forces the einsum path
        # (A/B benchmarking).
        from ..ops.pallas import (flash_attention_bthd,
                                  flash_attention_available)
        in_trace = ctx is not None
        try:
            min_t = int(_os.environ.get("MXTPU_FLASH_MIN_T", "512"))
        except ValueError:
            min_t = 512
        if (in_trace and self.dropout._rate == 0
                and _os.environ.get("MXTPU_DISABLE_FLASH", "0") != "1"
                and T >= min_t and T % 128 == 0
                and flash_attention_available() and trace_on_one_device()):
            return flash_attention_bthd(q, k, v, self._num_heads,
                                        scale=1.0 / math.sqrt(D),
                                        kv_mask=mask)
        q, k, v = (self._split_heads(F, a, B, T, D) for a in (q, k, v))
        scores = F.batch_dot(q, k, transpose_b=True) * (1.0 / math.sqrt(D))
        if mask is not None:
            neg = (1.0 - F.reshape(mask, shape=(B, 1, 1, T))) * -1e30
            scores = scores + neg
        attn = F.softmax(scores, axis=-1)
        attn = self.dropout(attn)
        return self._merge_heads(F, F.batch_dot(attn, v), B, T)

    def _ring_attend(self, q, k, v, mesh, T, D):
        """shard_map(axis_names={'sp'}) ring attention: sp is bound MANUAL
        (KV blocks rotate via ppermute, O(T_local) memory per device) while
        dp/tp shardings of the batch/head axes stay GSPMD-auto. Per-hop
        engine: the Pallas flash kernel when its tiling contract holds on
        this backend, dense einsum otherwise (the CPU virtual mesh)."""
        import functools
        import jax
        from jax.sharding import PartitionSpec as P
        from ..ops.pallas import flash_attention_available
        from ..parallel.ring_attention import (ring_attention,
                                               ring_flash_attention)
        sp = dict(mesh.shape)["sp"]
        t_local = T // sp
        scale = 1.0 / math.sqrt(D)
        use_flash = flash_attention_available() and (
            t_local % 128 == 0 if t_local > 128 else t_local % 8 == 0)
        spec = P(None, None, "sp", None)

        def fn(q, k, v):
            if use_flash:
                return ring_flash_attention(q, k, v, "sp", scale=scale)
            return ring_attention(q, k, v, "sp", scale=scale)

        # nested composition (e.g. inside the ZeRO-1 trainer's manual dp
        # region): the inner shard_map must see the ABSTRACT mesh already
        # in context, which carries the outer Manual axis marking
        use_mesh = mesh
        try:
            ctx_mesh = jax.sharding.get_abstract_mesh()
            if ctx_mesh is not None and not ctx_mesh.empty \
                    and ctx_mesh.axis_names == mesh.axis_names:
                use_mesh = ctx_mesh
        except Exception:  # mxlint: disable=broad-except — abstract
            # mesh probe across jax versions; concrete mesh fallback
            pass
        return jax.shard_map(fn, mesh=use_mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, axis_names={"sp"},
                             check_vma=False)(q, k, v)


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, activation="gelu",
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn_1 = nn.Dense(hidden_size, flatten=False, prefix="ffn1_")
            self.ffn_2 = nn.Dense(units, flatten=False, prefix="ffn2_")
            self.dropout = nn.Dropout(dropout)
            self._activation = activation

    def hybrid_forward(self, F, x):
        h = self.ffn_1(x)
        h = F.LeakyReLU(h, act_type="gelu") if self._activation == "gelu" \
            else F.Activation(h, act_type=self._activation)
        return self.dropout(self.ffn_2(h))


class TransformerEncoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = MultiHeadAttention(units, num_heads, dropout,
                                                prefix="attn_")
            self.ln1 = nn.LayerNorm(prefix="ln1_")
            self.ffn = PositionwiseFFN(units, hidden_size, dropout, prefix="ffn_")
            self.ln2 = nn.LayerNorm(prefix="ln2_")
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None):
        h = self.ln1(x + self.dropout(self.attention(x, mask)))
        return self.ln2(h + self.ffn(h))


class BERTEncoder(HybridBlock):
    """remat: rematerialize each layer in the backward (per-layer
    jax.checkpoint) — trades MXU recompute for activation HBM; a win for
    long-context memory, a measured loss at T=128 (BENCHMARKS.md).
    Resolved at CONSTRUCTION (None -> the MXTPU_BERT_REMAT env var), so
    the setting is a property of the model, not of whichever trace
    compiled first."""

    def __init__(self, num_layers, units, hidden_size, num_heads, dropout=0.0,
                 remat=None, **kwargs):
        super().__init__(**kwargs)
        import os as _os
        self._remat = (bool(remat) if remat is not None
                       else _os.environ.get("MXTPU_BERT_REMAT", "0") == "1")
        with self.name_scope():
            self.layers = nn.HybridSequential(prefix="layers_")
            for i in range(num_layers):
                self.layers.add(TransformerEncoderLayer(
                    units, hidden_size, num_heads, dropout,
                    prefix="layer%d_" % i))

    def hybrid_forward(self, F, x, mask=None):
        from .block_remat import maybe_remat_layer
        for layer in self.layers._children.values():
            if self._remat:
                x = maybe_remat_layer(layer, x, mask)
            else:
                x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """Token+segment+position embeddings -> encoder -> (sequence, pooled)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 token_type_vocab=2, dropout=0.1, remat=None, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units, prefix="word_")
            self.token_type_embed = nn.Embedding(token_type_vocab, units,
                                                 prefix="type_")
            self.position_embed = nn.Embedding(max_length, units, prefix="pos_")
            self.embed_ln = nn.LayerNorm(prefix="embln_")
            self.embed_dropout = nn.Dropout(dropout)
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout, remat=remat,
                                       prefix="enc_")
            self.pooler = nn.Dense(units, activation="tanh", flatten=False,
                                   prefix="pooler_")

    def hybrid_forward(self, F, token_ids, token_types=None, valid_mask=None):
        T = token_ids.shape[-1]
        positions = F.arange(0, T, dtype="int32")
        x = self.word_embed(token_ids)
        x = x + self.position_embed(positions)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.embed_dropout(self.embed_ln(x))
        seq = self.encoder(x, valid_mask)
        pooled = self.pooler(F.slice_axis(seq, axis=1, begin=0, end=1)
                             .reshape((token_ids.shape[0], self._units)))
        return seq, pooled


class BERTForPretrain(HybridBlock):
    """MLM + NSP heads over BERTModel (the benchmarked training config).

    When ``mlm_positions`` (B, M) int32 is given (KEYWORD-ONLY), the
    masked positions' hidden states are GATHERED before the
    transform/decoder so the 768x30522 vocab projection runs only on the
    ~15% masked slots — the reference decodes masked_positions the same
    way (GluonNLP BERTModel's ``masked_positions`` argument / reference
    `python/mxnet` pretraining recipe); decoding all T positions
    materializes a (B,T,V) logits tensor (1 GB at B=64 T=128 fp32) that
    the objective immediately discards. Without ``mlm_positions`` the
    full-sequence logits are returned (the fine-tune / scoring path).
    """

    def __init__(self, bert=None, vocab_size=30522, tie_decoder=False,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.bert = bert or BERTModel(vocab_size=vocab_size, **{})
            self.mlm_dense = nn.Dense(self.bert._units, activation="tanh",
                                      flatten=False, prefix="mlmd_")
            self.mlm_ln = nn.LayerNorm(prefix="mlmln_")
            if tie_decoder:
                # share the word-embedding matrix as the decoder weight
                # (GluonNLP BERT ties them; (V, units) serves both roles).
                # The absolute prefix aliases the decoder's "weight" slot
                # to the embedding's parameter.
                self.mlm_decoder = nn.Dense(
                    vocab_size, flatten=False,
                    in_units=self.bert._units,
                    params=self.bert.word_embed.params,
                    prefix=self.bert.word_embed.prefix)
            else:
                self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                            prefix="decoder_")
            self.nsp = nn.Dense(2, prefix="nsp_")

    def hybrid_forward(self, F, token_ids, token_types=None,
                       valid_mask=None, *, mlm_positions=None):
        # keyword-only: the pre-r4 positional contract (ids, types, mask)
        # keeps working; a mask can never silently land in the positions
        # slot (call sites that pipeline positional data through a trainer
        # wrap the model — see chip_smoke.py's _BertPretrainStep)
        seq, pooled = self.bert(token_ids, token_types, valid_mask)
        if mlm_positions is not None:
            B = token_ids.shape[0]
            M = mlm_positions.shape[1]
            rows = F.broadcast_to(
                F.reshape(F.arange(0, B, dtype="int32"), shape=(B, 1)),
                shape=(B, M))
            idx = F.stack(rows, mlm_positions, axis=0)      # (2, B, M)
            seq = F.gather_nd(seq, idx)                     # (B, M, units)
        mlm = self.mlm_decoder(self.mlm_ln(self.mlm_dense(seq)))
        nsp = self.nsp(pooled)
        return mlm, nsp


def bert_base(vocab_size=30522, dropout=0.1, **kwargs):
    cfg = dict(vocab_size=vocab_size, units=768, hidden_size=3072,
               num_layers=12, num_heads=12, dropout=dropout)
    cfg.update(kwargs)
    return BERTModel(**cfg)


def bert_large(vocab_size=30522, dropout=0.1, **kwargs):
    cfg = dict(vocab_size=vocab_size, units=1024, hidden_size=4096,
               num_layers=24, num_heads=16, dropout=dropout)
    cfg.update(kwargs)
    return BERTModel(**cfg)


def bert_sharding_rules(tp_axis="tp"):
    """Megatron-style tensor-parallel PartitionSpecs for ShardedTrainer:
    QKV/ffn1 column-parallel (shard output dim), proj/ffn2 row-parallel
    (shard input dim), embeddings sharded on vocab/hidden."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"(query|key|value)_weight$", P(tp_axis, None)),
        (r"ffn1_weight$", P(tp_axis, None)),
        (r"proj_weight$", P(None, tp_axis)),
        (r"ffn2_weight$", P(None, tp_axis)),
        (r"(query|key|value)_bias$", P(tp_axis)),
        (r"ffn1_bias$", P(tp_axis)),
        (r"word_weight$", P(tp_axis, None)),
        # untied decoder params; with tie_decoder=True the decoder weight
        # IS word_weight (rule above) and its bias lands under the
        # embedding prefix as word_bias — cover both namings
        (r"decoder_weight$", P(tp_axis, None)),
        (r"(decoder|word)_bias$", P(tp_axis)),
    ]
