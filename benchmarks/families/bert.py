"""BERT pretraining through the program's normal path.

``build_trainer`` is ``chip_smoke.bert_trainer`` (proven on the chip in
PR 22) with the sizes and the optimizer read from the configuration's
file: ``BERTForPretrain`` (gather-first masked-LM head, tied decoder) +
next-sentence head under ``parallel.ShardedTrainer``, AdamW, bfloat16
compute, float32 master weights, bfloat16 moments.
"""

import re

from .. import costs
from ..reference import bert as reference  # noqa: F401  (the runner's)

_BLOCK_PREFIX = re.compile(r"^[a-z]+\d+_")


def leaf_name(param_name):
    """'bertmodel0_enc_layer3_ln1_gamma' -> 'enc_layer3_ln1_gamma': the
    reference's name of the same leaf."""
    return _BLOCK_PREFIX.sub("", param_name, count=1)


def optimizer_params(cfg):
    hp = cfg["optimizer"]
    return {"learning_rate": hp["learning_rate"], "wd": hp["weight_decay"],
            "beta1": hp["beta1"], "beta2": hp["beta2"],
            "epsilon": hp["epsilon"]}


def build_trainer(cfg, mesh, rules=None, data_spec=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.models.bert import BERTForPretrain
    from incubator_mxnet_tpu.parallel import ShardedTrainer

    class _BertPretrainStep(HybridBlock):
        """Routes the trainer's positional data tuple to BERTForPretrain's
        keyword-only mlm_positions (gather-first MLM)."""

        def __init__(self, pretrain, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.pretrain = pretrain

        def hybrid_forward(self, F, token_ids, token_types, mlm_pos):
            return self.pretrain(token_ids, token_types,
                                 mlm_positions=mlm_pos)

    def loss_fn(out, mlab, nlab):
        mlm_logits, nsp_logits = out          # (B, n_mask, V), (B, 2)
        logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
        mlm_loss = -jnp.take_along_axis(logp, mlab[:, :, None],
                                        axis=-1).mean()
        nlogp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
        nsp_loss = -jnp.take_along_axis(nlogp, nlab[:, None], axis=-1).mean()
        return mlm_loss + nsp_loss

    vocab = cfg["vocab_size"]
    net = _BertPretrainStep(BERTForPretrain(
        bert=mx.models.BERTModel(
            vocab_size=vocab, units=cfg["hidden_size"],
            hidden_size=cfg["intermediate_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            max_length=cfg["max_position_embeddings"],
            token_type_vocab=cfg["type_vocab_size"], dropout=0.0),
        vocab_size=vocab, tie_decoder=True))
    net.initialize(mx.init.Normal(0.02))
    # one tiny eager forward materializes the deferred shapes
    net(mx.nd.array(np.zeros((1, 8), np.int32)),
        mx.nd.array(np.zeros((1, 8), np.int32)),
        mx.nd.array(np.zeros((1, 2), np.int32)))
    spec = P(data_spec) if data_spec else P()
    return ShardedTrainer(net, loss_fn, mesh, rules=rules, optimizer="adamw",
                          optimizer_params=optimizer_params(cfg),
                          data_specs=[spec, spec, spec], label_spec=spec,
                          compute_dtype=cfg["compute_dtype"],
                          opt_state_dtype=cfg["moment_dtype"])


def sharding_rules(name):
    """The named rule sets a traffic file may ask for."""
    from incubator_mxnet_tpu.models.bert import bert_sharding_rules
    return {None: None, "bert_tp": bert_sharding_rules("tp")}[name]


def train_flops_per_token(cfg, traffic):
    return costs.bert_train_flops_per_token(
        cfg, traffic["seq_len"], traffic["mlm_positions"])
