"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py            # one chip: phases `train` and `generate`
    python chip_smoke.py --chips 4  # four chips: phase `mesh`, nothing else

One process, no children. Exits non-zero, printing no result, unless
``jax.devices()[0].platform == "tpu"``. Every check is fatal: a phase that
raises ends the run. The figures printed on the way are SMOKE NUMBERS — a
few steps of one repeated batch — not benchmark results. The last line of
standard output is the contract's JSON object and nothing more.

Phase `train` — the BERT-base pretrain step (12 layers, 768 units, 12
heads, FFN 3072, vocab 30522, tied decoder, gather-first MLM head + NSP; AdamW, bf16
compute, bf16-stored moments) through ``parallel.ShardedTrainer`` on a
one-device mesh, at B=64,T=128 (38 kernels: 26 fused LayerNorm + 12 fused
softmax) and at B=16,T=512 (50: the one-tile flash-attention launches,
one forward and one backward a layer, in the softmax's place; the step-0
loss is compared with the dense-attention path on the same parameters).
The optimizer is applied leaf by leaf by XLA: no packed launch.

Phase `generate` — GPT-2-small widths through ``GenerateEngine.generate``
over ``GPTPagedLM``, in-process; each prompt's first generated token is
held against the full-sequence oracle ``gpt_logits``.

Phase `mesh` (``--chips 4``) — the same BERT-base step at B=64,T=128 on
``make_mesh({"dp": 2, "tp": 2})`` with ``bert_sharding_rules("tp")``,
compared with the one-device step on the same parameters and batch.
"""

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

VOCAB = 30522
MASK_FRAC = 0.15
# bf16 compute: two roundings of the same f32 loss (~10.4 + 0.7) through
# different attention kernels / different reduction orders across shards
LOSS_RTOL = 2e-2


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


# --------------------------------------------------------------- phase train
def bert_batch(batch, seqlen):
    """A synthetic pretraining batch from a fixed seed: (data, label)
    lists of host arrays."""
    rng = np.random.RandomState(0)
    n_mask = max(1, int(seqlen * MASK_FRAC))
    ids = rng.randint(0, VOCAB, (batch, seqlen)).astype(np.int32)
    types = np.zeros((batch, seqlen), np.int32)
    mlm_pos = np.stack([rng.permutation(seqlen)[:n_mask]
                        for _ in range(batch)]).astype(np.int32)
    mlm_lab = np.take_along_axis(ids, mlm_pos, axis=1)
    ids_masked = ids.copy()
    np.put_along_axis(ids_masked, mlm_pos, 103, axis=1)   # [MASK] id
    nsp_lab = rng.randint(0, 2, (batch,)).astype(np.int32)
    return [ids_masked, types, mlm_pos], [mlm_lab, nsp_lab]


def bert_trainer(mesh, rules=None, data_spec=None, num_layers=12):
    """BERT-base pretraining under ShardedTrainer.
    Parameters come from a fixed seed, so two calls give equal weights."""
    import jax
    import jax.numpy as jnp
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

    np.random.seed(0)
    mx.random.seed(0)
    net = _BertPretrainStep(BERTForPretrain(
        bert=mx.models.bert_base(vocab_size=VOCAB, dropout=0.0,
                                 max_length=512, num_layers=num_layers),
        vocab_size=VOCAB, tie_decoder=True))
    net.initialize(mx.init.Normal(0.02))
    # one tiny eager forward materializes the deferred shapes
    net(mx.nd.array(np.zeros((1, 8), np.int32)),
        mx.nd.array(np.zeros((1, 8), np.int32)),
        mx.nd.array(np.zeros((1, 2), np.int32)))
    spec = data_spec if data_spec is not None else P()
    return ShardedTrainer(net, loss_fn, mesh, rules=rules, optimizer="adamw",
                          optimizer_params={"learning_rate": 1e-4},
                          data_specs=[spec, spec, spec], label_spec=spec,
                          compute_dtype="bfloat16",
                          opt_state_dtype="bfloat16")


def kernel_calls(trainer, data, label):
    """How many Pallas kernels the step program carries. Counted in the
    lowered program, the one handed to the chip's compiler: a kernel that
    gave way to a lax reference is simply not there."""
    return trainer.lowered(data, label).as_text().count("tpu_custom_call")


def run_steps(trainer, data, label, n_steps):
    """n_steps on one repeated batch -> (losses, seconds per step); the
    first step's seconds include trace and compile."""
    import jax
    losses, secs = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(trainer.step(data, label))
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, secs


def check_training(losses, what):
    """Finite, and lower at the end than at the start. Not monotone: AdamW
    at lr 1e-4 with no warm-up overshoots once in its first steps (the CPU
    per-parameter path shows the same rise, to four digits)."""
    check(all(np.isfinite(losses)), "%s: non-finite loss %r" % (what, losses))
    check(losses[-1] < losses[0],
          "%s: loss did not fall: %r" % (what, losses))


def step_report(losses, secs, batch, seqlen):
    import jax
    med = float(np.median(secs[1:]))
    stats = jax.devices()[0].memory_stats() or {}
    return {"losses": [round(l, 4) for l in losses],
            "compile_seconds": round(secs[0] - med, 2),
            "step_ms": [round(t * 1e3, 1) for t in secs[1:]],
            "median_step_ms": round(med * 1e3, 2),
            "tokens_per_sec": round(batch * seqlen / med, 1),
            "process_peak_bytes_in_use": stats.get("peak_bytes_in_use")}


@contextlib.contextmanager
def dense_attention():
    """Flash attention switched off for what is traced inside: the repo's
    existing MXTPU_DISABLE_FLASH switch (models/bert.py)."""
    os.environ["MXTPU_DISABLE_FLASH"] = "1"
    try:
        yield
    finally:
        del os.environ["MXTPU_DISABLE_FLASH"]


def phase_train(shapes=((64, 128, 6), (16, 512, 8)), num_layers=12):
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.parallel import make_mesh
    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    for batch, seqlen, n_steps in shapes:
        what = "train B=%d T=%d" % (batch, seqlen)
        host_data, host_label = bert_batch(batch, seqlen)
        data = [mx.nd.array(a) for a in host_data]
        label = [mx.nd.array(a) for a in host_label]
        tr = bert_trainer(mesh, num_layers=num_layers)
        n_kernels = kernel_calls(tr, data, label)
        # fused LayerNorm twice a layer + embedding + MLM head, and a layer's
        # attention: one fused softmax, from T=512 flash attention fwd + bwd
        # (38 kernels at T=128, 50 at T=512)
        check(n_kernels >= 3 * num_layers + 2,
              "%s: only %d tpu_custom_call in the step program — a kernel "
              "gave way to its reference" % (what, n_kernels))
        dense_loss = None
        if seqlen >= 512:
            # the dense path on the trainer's own parameters, untouched:
            # audit_step compiles without donation and commits nothing
            with dense_attention():
                n_dense = kernel_calls(tr, data, label)
                _counts, dense_loss = tr.audit_step(data, label)
            check(n_kernels > n_dense,
                  "%s: the flash kernel is not in the step program "
                  "(%d kernels with it, %d without)"
                  % (what, n_kernels, n_dense))
        losses, secs = run_steps(tr, data, label, n_steps)
        check_training(losses, what)
        report = step_report(losses, secs, batch, seqlen)
        if dense_loss is not None:
            check(abs(losses[0] - dense_loss) <= LOSS_RTOL * abs(dense_loss),
                  "%s: flash step-0 loss %r vs dense attention %r"
                  % (what, losses[0], dense_loss))
            report["dense_attention_loss0"] = round(dense_loss, 4)
        say("train", smoke_numbers_not_results=True, batch=batch,
            seqlen=seqlen, layers=num_layers, tpu_custom_calls=n_kernels,
            **report)
        del tr


# ------------------------------------------------------------ phase generate
def phase_generate(config=None, n_prompts=4, prompt_len=64, new_tokens=16):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.generate import GenerateEngine, GPTPagedLM
    from incubator_mxnet_tpu.models.gpt import (gpt_config, gpt_logits,
                                                gpt_param_shapes)
    cfg = gpt_config(config or {"vocab_size": 50257, "units": 768,
                                "num_layers": 12, "num_heads": 12,
                                "max_len": 1024})
    rng = np.random.RandomState(0)
    params = {n: (rng.randn(*s) * 0.02).astype(np.float32)
              for n, s in gpt_param_shapes(cfg).items()}
    model = GPTPagedLM(params, cfg)           # use_kernel=False, as shipped
    engine = GenerateEngine(
        model, model.make_cache(n_prompts, max_len=prompt_len + new_tokens))
    prompts = [rng.randint(1, cfg["vocab_size"], prompt_len).tolist()
               for _ in range(n_prompts)]
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=new_tokens)
    first_seconds = time.perf_counter() - t0      # compiles included
    t0 = time.perf_counter()
    again = engine.generate(prompts, max_new_tokens=new_tokens)
    warm_seconds = time.perf_counter() - t0
    check(all(len(o) == new_tokens for o in outs),
          "generate: wrong lengths %r" % [len(o) for o in outs])
    check(all(0 <= t < cfg["vocab_size"] for o in outs for t in o),
          "generate: token out of range")
    check(outs == again, "generate: greedy decode is not repeatable")
    # the oracle: one full-sequence causal forward on the chip
    logits = np.asarray(jax.jit(
        lambda p, t: gpt_logits(p, cfg, t)[:, -1])(
            model.params, jnp.asarray(prompts, jnp.int32)))
    check(np.isfinite(logits).all(), "generate: oracle logits not finite")
    exact = 0
    for row, out in zip(logits, outs):
        # equal to the oracle's argmax — or, where the oracle's two best
        # are tied to within what two f32 programs of different shape
        # round apart on the MXU, one of the tied
        spread = float(row.max() - np.median(row))
        behind = float(row.max() - row[out[0]]) / spread
        exact += int(out[0] == int(row.argmax()))
        check(behind <= 0.02,
              "generate: first token %d is %.3f of the logit spread behind "
              "the oracle's argmax %d" % (out[0], behind, int(row.argmax())))
    say("generate", smoke_numbers_not_results=True, prompts=n_prompts,
        prompt_len=prompt_len, new_tokens=new_tokens,
        layers=cfg["num_layers"],
        first_tokens=[o[0] for o in outs],
        first_tokens_equal_oracle_argmax="%d/%d" % (exact, n_prompts),
        first_call_seconds=round(first_seconds, 2),
        warm_call_seconds=round(warm_seconds, 2),
        warm_decode_tokens_per_sec=round(
            engine.last_stats["decode_tokens"]
            / engine.last_stats["decode_seconds"], 1))


# ---------------------------------------------------------------- phase mesh
def phase_mesh(batch=64, seqlen=128, n_steps=6, num_layers=12):
    """dp2 x tp2 step against the one-device step: same seed-made
    parameters, same batch. Six steps, so that the median step time does
    not rest on the mesh program's one-off slow second call."""
    import jax
    from jax.sharding import PartitionSpec as P
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.bert import bert_sharding_rules
    from incubator_mxnet_tpu.parallel import make_mesh
    devices = jax.devices()[:4]
    host_data, host_label = bert_batch(batch, seqlen)
    data = [mx.nd.array(a) for a in host_data]
    label = [mx.nd.array(a) for a in host_label]

    one = bert_trainer(make_mesh({"dp": 1}, devices=devices[:1]),
                       num_layers=num_layers)
    one_losses, one_secs = run_steps(one, data, label, n_steps)
    check_training(one_losses, "mesh: one-device step")
    del one

    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devices)
    tr = bert_trainer(mesh, rules=bert_sharding_rules("tp"),
                      data_spec=P("dp"), num_layers=num_layers)
    n_kernels = kernel_calls(tr, data, label)
    losses, secs = run_steps(tr, data, label, n_steps)
    check_training(losses, "mesh: dp2 x tp2 step")
    check(abs(losses[0] - one_losses[0]) <= LOSS_RTOL * abs(one_losses[0]),
          "mesh: step-0 loss %r on dp2 x tp2 vs %r on one device"
          % (losses[0], one_losses[0]))
    # the state really spreads: code that never ran on more than one chip
    # may put everything on device 0
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    check(all(b > 0 for b in in_use),
          "mesh: a device holds nothing: bytes_in_use=%r" % in_use)
    name, w = next((n, v) for n, v in sorted(tr.param_values.items())
                   if n.endswith("ffn1_weight"))
    shards = w.addressable_shards
    check(len(shards) == 4 and len({s.device for s in shards}) == 4
          and shards[0].data.shape[0] * 2 == w.shape[0],
          "mesh: %s is not split over tp on four devices: %r"
          % (name, [(s.device, s.data.shape) for s in shards]))
    say("mesh", smoke_numbers_not_results=True, mesh={"dp": 2, "tp": 2},
        batch=batch, seqlen=seqlen, layers=num_layers,
        tpu_custom_calls=n_kernels,
        one_device=step_report(one_losses, one_secs, batch, seqlen),
        **step_report(losses, secs, batch, seqlen),
        bytes_in_use_per_device=in_use,
        tp_weight={"name": name, "shape": list(w.shape),
                   "shard_shape": list(shards[0].data.shape)})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp2 x tp2 step and the one-device "
                         "step it is compared with")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("chip_smoke: needs a TPU, jax found %r" % devices[0].platform,
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print("chip_smoke: --chips %d but jax found %d device(s)"
              % (args.chips, len(devices)), file=sys.stderr)
        return 2
    from incubator_mxnet_tpu import compilecache
    say("start", compile_cache_dir=compilecache.use_jax_cache(),
        jax=jax.__version__, devices=len(devices),
        device_kind=devices[0].device_kind)
    if args.chips == 4:
        phase_mesh()
    else:
        phase_train()
        phase_generate()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
