"""Operations and bytes the algorithms need, from the shapes alone.

Counted once, by hand, from the published layer equations: matrix
multiplications and attention only; no optimizer arithmetic, nothing
recomputed, no elementwise work. A multiply-add is two operations.
Configuration dicts are the files under ``benchmarks/configs/``.
"""


def bert_layer_forward_flops_per_token(cfg, seq_len):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    dense = 2 * (4 * d * d + 2 * d * f)      # q, k, v, proj; ffn1, ffn2
    attention = 2 * 2 * seq_len * d          # q.k^T and p.v, all heads
    return dense + attention


def bert_forward_flops_per_sequence(cfg, seq_len, mlm_positions):
    """One sequence through the encoder, the gather-first MLM head (only
    the masked positions reach the vocabulary projection) and NSP."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    encoder = (cfg["num_hidden_layers"] * seq_len
               * bert_layer_forward_flops_per_token(cfg, seq_len))
    mlm_head = mlm_positions * (2 * d * d + 2 * d * v)
    nsp_head = 2 * d * d + 2 * d * 2         # pooler, classifier
    return encoder + mlm_head + nsp_head


def bert_train_flops_per_token(cfg, seq_len, mlm_positions):
    """Forward and backward: the backward pass of a matrix product is
    two products of the same size, so three times the forward."""
    return 3.0 * bert_forward_flops_per_sequence(
        cfg, seq_len, mlm_positions) / seq_len


def bert_param_count(cfg):
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layer = 4 * (d * d + d) + (d * f + f) + (f * d + d) + 4 * d
    embed = (v + cfg["max_position_embeddings"] + cfg["type_vocab_size"]) * d \
        + 2 * d
    heads = (d * d + d) + (d * d + d) + 2 * d + v + (2 * d + 2)
    return cfg["num_hidden_layers"] * layer + embed + heads


def flash_attention_flops(batch, heads, seq_len, head_dim, backward):
    """Non-causal attention over one (batch, heads, T, D) call. Forward:
    q.k^T and p.v. Backward: dv, dp, dq, dk; the recomputed q.k^T is
    the kernel's own choice and is not counted."""
    product = 2 * batch * heads * seq_len * seq_len * head_dim
    return (4 if backward else 2) * product


def flash_attention_bytes(batch, heads, seq_len, head_dim, backward,
                          itemsize=2):
    """q, k, v read and o written once forward; backward reads q, k, v,
    o, do and writes dq, dk, dv."""
    tensor = batch * heads * seq_len * head_dim * itemsize
    return (8 if backward else 4) * tensor


def fused_adamw_bytes(n_params, itemsize=4):
    """The packed launch reads weight, gradient and both moments and
    writes weight and both moments, all in the packed buffers' type."""
    return 7 * n_params * itemsize


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take: whichever bound is longer."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def gpt_param_count(cfg):
    d, v = cfg["n_embd"], cfg["vocab_size"]
    f = 4 * d
    layer = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) \
        + 4 * d
    return cfg["n_layer"] * layer + (v + cfg["n_positions"]) * d + 2 * d


def gpt_decode_step_floor_seconds(cfg, rows, live_positions, peaks,
                                  itemsize=4):
    """One decode step of `rows` sequences: every weight and the live
    keys and values are read once, or 2 x parameters x rows operations
    run at the peak, whichever takes longer."""
    n = gpt_param_count(cfg)
    kv = 2 * cfg["n_layer"] * live_positions * cfg["n_embd"] * itemsize
    return roofline_seconds(2.0 * n * rows, n * itemsize + kv, peaks)
