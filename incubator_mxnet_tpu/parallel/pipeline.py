"""Pipeline parallelism over a `pp` mesh axis — the TPU-native form.

The reference scales pipelines by process placement (one worker per
stage over ps-lite/NCCL); here the WHOLE pipeline is one SPMD program:
every stage has identical structure (the homogeneous-layer case —
transformer blocks, MLP stacks), stage weights are STACKED on a leading
axis sharded over `pp`, and a `lax.scan` over the GPipe schedule shifts
activations to the next stage with `lax.ppermute` each tick. Because
`ppermute` and `scan` are differentiable, `jax.grad` through
`pipeline_apply` IS the backward pipeline (reverse schedule, reversed
permutes) — no hand-written 1F1B machinery.

Schedule: M microbatches through S stages takes M + S - 1 ticks; device
s computes its stage every tick (idle ticks feed garbage that is never
read — the standard bubble, fraction (S-1)/(M+S-1)).

Interleaving (`interleave=v`, the Megatron "virtual pipeline" schedule):
each device owns v stage CHUNKS assigned round-robin (device s holds
global stages s, S+s, 2S+s, ...), activations ride the ring v times, and
the scan runs v*M + S - 1 ticks of one-chunk cost instead of M + S - 1
ticks of v-chunk cost — fill/drain cost drops from v*c*(S-1) to c*(S-1),
the bubble cut by exactly v. The total compute is identical (v*M busy
ticks per device); only the idle triangle shrinks.

Heterogeneous ends (`pre_fn`/`post_fn`): an embedding applied at the
microbatch injection point and a head applied at the stash point run
INSIDE the scanned region, once per microbatch. Their win is memory, not
FLOPs: the head sees (B/M, ...) slices, so e.g. LM logits peak at 1/M of
the outside-the-region materialization. (SPMD cost model: every device
evaluates the pre/post select each tick, so keep them small relative to
a stage tick — the classic per-device placement of embed/head is a
process-placement concept that does not exist in a single SPMD program.)
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params", "PipelineStack"]


def stack_stage_params(per_stage_params, mesh=None, axis="pp", interleave=1):
    """[params_stage0, params_stage1, ...] (matching pytrees) -> one
    pytree with a leading stage axis, device_put sharded over `axis`
    when a mesh is given.

    With ``interleave=v`` the list length must be v*S and leaves come out
    shaped (v, S, ...) with the SECOND axis sharded over `axis`, so that
    device s holds global stages s, S+s, 2S+s, ... (the round-robin chunk
    assignment the interleaved schedule needs)."""
    v = int(interleave)
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)
    if v > 1:
        n = len(per_stage_params)
        if n % v:
            raise ValueError("interleave=%d does not divide %d stages"
                             % (v, n))
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape((v, n // v) + a.shape[1:]), stacked)
    if mesh is not None:
        def put(x):
            if v > 1:
                spec = P(None, axis, *([None] * (x.ndim - 2)))
            else:
                spec = P(axis, *([None] * (x.ndim - 1)))
            return jax.device_put(x, NamedSharding(mesh, spec))
        stacked = jax.tree_util.tree_map(put, stacked)
    return stacked


def pipeline_apply(stage_fn, stacked_params, x, mesh, axis="pp",
                   n_microbatch=None, remat=False, interleave=1,
                   pre_fn=None, pre_params=None,
                   post_fn=None, post_params=None, post_batched=None):
    """Run `x` through S (or v*S interleaved) pipelined stages.

    stage_fn : (stage_params, activations) -> activations, same shape
        (the homogeneous-trunk contract).
    stacked_params : pytree with leading stage axis S sharded over
        `axis` — or, with ``interleave=v``, shape (v, S, ...) with the
        SECOND axis sharded (see stack_stage_params).
    x : (B, ...) global batch; split into `n_microbatch` microbatches
        (default: the pp degree) along axis 0.
    remat : rematerialize each (stage, tick) in the backward instead of
        storing its internals. The 1F1B schedule's POINT on GPU pipelines
        is bounding live activations at ~S microbatches instead of M; in
        the scanned SPMD formulation the same memory profile falls out of
        remat (scan saves only the per-tick carry, stage internals are
        recomputed).
    interleave : v > 1 runs the Megatron virtual-pipeline schedule —
        v chunks per device, v*M + S - 1 one-chunk ticks, bubble cost cut
        by v vs GPipe over the same v*S stages (module docstring).
    pre_fn / post_fn : optional heterogeneous END stages run inside the
        scanned region. ``pre_fn(pre_params, microbatch)`` maps the raw
        feed to the trunk activation shape at the injection point (an
        embedding); ``post_fn(post_params, activations)`` maps the trunk
        output at the stash point (a head / per-microbatch loss), so its
        intermediates peak at one microbatch, 1/M of the whole-batch
        materialization. Both differentiable; their grads psum over the
        region transpose.
    post_batched : whether post_fn's output keeps the microbatch slice as
        its leading dim (True -> result reshapes to (B, ...); False ->
        the per-microbatch (M, ...) stack is returned, e.g. a loss head).
        Default None infers from the output shape — pass it explicitly
        when the head output's leading dim could coincidentally equal
        B // n_microbatch.
    Returns (B, ...) outputs (post_fn's shape when given). Differentiable
    end to end.
    """
    S = mesh.shape[axis]
    v = int(interleave)
    if v < 1:
        raise ValueError("interleave must be >= 1")
    M = int(n_microbatch or S)
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    B = x.shape[0]
    if B % M:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (B, M))
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if v == 1:
        n_stages = {a.shape[0] for a in leaves}
        if n_stages != {S}:
            raise ValueError(
                "stacked stage axis %s must equal the %r mesh degree %d — "
                "each device runs exactly ONE stage"
                % (sorted(n_stages), axis, S))
    else:
        heads = {a.shape[:2] for a in leaves}
        if heads != {(v, S)}:
            raise ValueError(
                "interleave=%d needs stacked leaves shaped (v, S, ...) = "
                "(%d, %d, ...); got %s" % (v, v, S, sorted(heads)))
    mb = x.reshape((M, B // M) + x.shape[1:])

    if v == 1:
        param_specs = jax.tree_util.tree_map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params)
    else:
        param_specs = jax.tree_util.tree_map(
            lambda a: P(None, axis, *([None] * (a.ndim - 2))),
            stacked_params)
    has_pre, has_post = pre_fn is not None, post_fn is not None
    pre_params = pre_params if has_pre else ()
    post_params = post_params if has_post else ()
    # trunk activation / stash shapes (microbatch granularity)
    act_shape = jax.eval_shape(pre_fn, pre_params, mb[0]) if has_pre \
        else jax.eval_shape(lambda a: a, mb[0])
    out_shape = jax.eval_shape(post_fn, post_params,
                               act_shape) if has_post else act_shape
    # schedule length: last microbatch M-1 leaves chunk v-1 of device S-1
    q_last, i_last = divmod(M - 1, S)
    T = q_last * v * S + i_last + (v - 1) * S + S

    def manual(params, pre_p, post_p, mb):
        # params: this device's stage slice (leading sharded axis length 1)
        if v == 1:
            local = jax.tree_util.tree_map(lambda a: a[0], params)
        else:
            local = jax.tree_util.tree_map(lambda a: a[:, 0], params)
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outs = carry
            # this device's slot at tick t: stage-time u, microbatch
            # m = q*S + i, chunk r — u < 0 / m >= M slots carry garbage
            # that is never injected into feeds or stashed into outs
            u = t - idx
            i = jnp.mod(u, S)
            w = (u - i) // S
            r = jnp.mod(w, v)
            q = w // v
            m = q * S + i
            feed = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(m, 0, M - 1), axis=0, keepdims=False)
            if has_pre:
                feed = pre_fn(pre_p, feed)
            inject = (idx == 0) & (r == 0) & (u >= 0) & (m < M)
            x_in = jnp.where(inject, feed, state)
            if v == 1:
                chunk = local
            else:
                chunk = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, jnp.clip(r, 0, v - 1), axis=0, keepdims=False),
                    local)
            y = stage_fn(chunk, x_in)
            # the LAST chunk of the LAST device finishes microbatch m;
            # stash (through the head, when given) before the shift
            take = (idx == S - 1) & (r == v - 1) & (u >= 0) & (m < M)
            stash = post_fn(post_p, y) if has_post else y
            outs = jax.lax.cond(
                take,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, stash, jnp.clip(m, 0, M - 1), axis=0),
                lambda o: o, outs)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        state0 = jnp.zeros(act_shape.shape, act_shape.dtype)
        outs0 = jnp.zeros((M,) + out_shape.shape, out_shape.dtype)
        (state, outs), _ = jax.lax.scan(tick, (state0, outs0),
                                        jnp.arange(T))
        # outs live on the last stage only; rotate them to every device so
        # the result leaves the region replicated over pp
        outs = jax.lax.psum(
            jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    # nested composition (e.g. inside the ZeRO-1 trainer's manual dp
    # region): shard_map requires the ABSTRACT mesh already in context —
    # axis types there carry the outer Manual marking the concrete Mesh
    # lacks
    use_mesh = mesh
    try:
        ctx_mesh = jax.sharding.get_abstract_mesh()
        if ctx_mesh is not None and ctx_mesh.axis_names == mesh.axis_names \
                and not ctx_mesh.empty:
            use_mesh = ctx_mesh
    except Exception:  # mxlint: disable=broad-except — abstract mesh
        # probe across jax versions; the concrete mesh still works
        pass
    rep_specs = jax.tree_util.tree_map(lambda a: P(), (pre_params,
                                                       post_params))
    out = jax.shard_map(
        manual, mesh=use_mesh,
        in_specs=(param_specs, rep_specs[0], rep_specs[1], P()),
        out_specs=P(),
        axis_names={axis}, check_vma=False,
    )(stacked_params, pre_params, post_params, mb)
    # (M, B/M, ...) -> (B, ...) when the per-microbatch output keeps the
    # batch slice as its leading dim; otherwise (per-microbatch scalars,
    # e.g. a loss head) hand back the (M, ...) stack as-is
    batched = post_batched
    if batched is None:
        batched = out.ndim >= 2 and out.shape[1] == B // M
    if batched:
        if out.ndim < 2 or out.shape[1] != B // M:
            raise ValueError(
                "post_batched=True but post_fn output %s does not keep the "
                "(B/M,)=(%d,) microbatch slice as its leading dim"
                % (out.shape[1:], B // M))
        return out.reshape((B,) + out.shape[2:])
    return out


from ..gluon.block import HybridBlock, _TraceCtx, _trace_state, \
    current_trace


class PipelineStack(HybridBlock):
    """Homogeneous trunk pipelined over the mesh's ``pp`` axis — the
    composition point between gluon models and pipeline_apply
    (VERDICT r3 #5: pp BEHIND the Trainer API, not beside it).

    ``stage_factory(i)`` must build structurally identical blocks
    (e.g. transformer encoder layers); they register as ordinary gluon
    children (normal init/checkpoint/export). Under a ShardedTrainer
    whose mesh carries the ``pp`` axis with degree == n_stages, the
    forward stacks each stage's parameters on a leading pp-sharded
    axis and runs the scanned GPipe schedule (pipeline_apply — one
    SPMD program, collective-permute shifts); in every other context
    (eager, export, pp absent or degree 1) the stages run
    sequentially, bit-identical semantics.

    Contract: stages are single-input/single-output with matching
    shapes; use LayerNorm rather than BatchNorm inside stages (batch
    aux-state updates do not cross the pipelined region); dropout must
    be 0 in stages AND in the in-region ``embed``/``head`` blocks
    (microbatch RNG streams are not threaded through the schedule — a
    Dropout there would reuse one trace-time mask every tick under a pp
    mesh while getting fresh masks on the off-mesh path).
    """

    def __init__(self, stage_factory, n_stages, pp_axis="pp",
                 n_microbatch=None, remat=False, interleave=1,
                 embed=None, head=None, head_batched=True,
                 stage_rules=None, **kwargs):
        super().__init__(**kwargs)
        self._pp_axis = pp_axis
        self._n_micro = n_microbatch
        self._remat = bool(remat)
        self._interleave = int(interleave)
        # tensor parallelism INSIDE the pipelined stages (dp x tp x pp —
        # the standard large-model composition): [(regex, PartitionSpec)]
        # over a stage's OWN param dims; the stacked leaf gets the spec
        # shifted right of the pp stage axis, pp stays the shard_map
        # manual axis and tp rides GSPMD-auto through the stage matmuls.
        # Pass the SAME rules to ShardedTrainer so resting params and
        # optimizer state shard over tp too.
        self._stage_rules = stage_rules
        if stage_rules is not None:
            from .trainer import sharding_rules
            self._stage_matcher = sharding_rules(stage_rules)
        else:
            self._stage_matcher = None
        # head_batched=False declares a batch-reducing head (per-microbatch
        # outputs); requires n_microbatch so the off-mesh fallback can
        # reproduce the same (M, ...) result shape
        self._head_batched = bool(head_batched)
        if not self._head_batched and not n_microbatch:
            raise ValueError("head_batched=False requires an explicit "
                             "n_microbatch (the fallback path must split "
                             "the batch identically)")
        self._stage_blocks = []
        with self.name_scope():
            for i in range(n_stages):
                blk = stage_factory(i)
                setattr(self, "stage%d" % i, blk)
                self._stage_blocks.append(blk)
            # Block.__setattr__ registers Block-valued attributes as
            # children, so these assignments also wire up init/checkpoint
            self._embed_block = embed
            self._head_block = head

    def _block_runner(self, block, outer):
        """(param_leaves, act) -> block(act) under a trace ctx whose
        param_map carries `param_leaves` for the block's own names."""
        names = sorted(p.name for p in block.collect_params().values())

        def run(leaves, act):
            # mesh_ctx rides into the stage trace so mesh-aware blocks
            # (ring attention over sp, MoE ep constraints) can bind their
            # OWN manual axes nested inside the pp region
            inner = _TraceCtx({**outer.param_map, **dict(zip(names, leaves))},
                              None, outer.training,
                              mesh_ctx=outer.mesh_ctx)
            prev = getattr(_trace_state, "ctx", None)
            _trace_state.ctx = inner
            try:
                return block.forward(act)
            finally:
                _trace_state.ctx = prev
        return run, [outer.param_map[n] for n in names]

    def hybrid_forward(self, F, x):
        ctx = current_trace()
        mesh = getattr(ctx, "mesh_ctx", None) if ctx is not None else None
        stages = self._stage_blocks
        axis = self._pp_axis
        if (mesh is None or axis not in mesh.axis_names
                or dict(mesh.shape)[axis] == 1):
            if self._embed_block is not None:
                x = self._embed_block(x)
            for st in stages:
                x = st(x)
            if self._head_block is not None:
                if self._head_batched:
                    x = self._head_block(x)
                else:
                    # batch-reducing head: mirror the pipelined path's
                    # per-microbatch application and (M, ...) stacking
                    M = int(self._n_micro)
                    if x.shape[0] % M:
                        raise ValueError(
                            "batch %d not divisible into %d microbatches"
                            % (x.shape[0], M))
                    b = x.shape[0] // M
                    mbs = [self._head_block(x[j * b:(j + 1) * b])
                           for j in range(M)]
                    wrap_nd = hasattr(mbs[0], "_data")
                    x = jnp.stack([m._data if wrap_nd else m for m in mbs])
                    if wrap_nd:
                        from ..ndarray import NDArray
                        x = NDArray(x)
            return x
        S = dict(mesh.shape)[axis]
        v = self._interleave
        if S * v != len(stages):
            raise ValueError(
                "PipelineStack has %d stages but mesh axis %r degree %d x "
                "interleave %d covers %d — each device runs exactly "
                "interleave chunks" % (len(stages), axis, S, v, S * v))
        names = [sorted(p.name for p in st.collect_params().values())
                 for st in stages]
        if v == 1:
            stacked = [jnp.stack([ctx.param_map[names[s][k]]
                                  for s in range(S)])
                       for k in range(len(names[0]))]
        else:
            # round-robin chunk assignment: leaf[r, s] = stage r*S + s
            stacked = [jnp.stack([jnp.stack([ctx.param_map[names[r * S + s][k]]
                                             for s in range(S)])
                                  for r in range(v)])
                       for k in range(len(names[0]))]
        if self._stage_matcher is not None:
            # pin tp (or any non-pp) shardings onto the stacked leaves:
            # lead with the stage axis ((None,) pp for v>1), then the
            # user's per-stage-param spec
            lead = (None, axis) if v > 1 else (axis,)
            pinned = []
            for k, leaf in enumerate(stacked):
                spec = tuple(self._stage_matcher(names[0][k]))
                if spec and any(ax is not None for ax in spec):
                    leaf = jax.lax.with_sharding_constraint(
                        leaf, NamedSharding(mesh, P(*lead, *spec)))
                pinned.append(leaf)
            stacked = pinned
        outer = ctx
        stage_fn, _ = self._block_runner(stages[0], outer)

        pre_fn = pre_p = post_fn = post_p = None
        if self._embed_block is not None:
            pre_fn, pre_p = self._block_runner(self._embed_block, outer)
        if self._head_block is not None:
            post_fn, post_p = self._block_runner(self._head_block, outer)

        return pipeline_apply(stage_fn, stacked, x, mesh, axis=axis,
                              n_microbatch=self._n_micro,
                              remat=self._remat, interleave=v,
                              pre_fn=pre_fn, pre_params=pre_p,
                              post_fn=post_fn, post_params=post_p,
                              post_batched=(self._head_batched
                                            if self._head_block is not None
                                            else None))
