"""ShardedTrainer — one pjit program for the whole training step.

This is the TPU-idiomatic replacement for the reference's
Trainer+KVStore('device'/'nccl'/'dist') stack (SURVEY §2.4): instead of
pushing gradients key-by-key through a store, the ENTIRE step
(forward + backward + optimizer) is one XLA program over a Mesh; parameter/
activation PartitionSpecs make XLA insert the dp gradient psum and tp/sp
collectives over ICI automatically (GSPMD).
"""

import re
import time

import numpy as _np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compilecache import aot as _aot
from ..compilecache import store as _ccstore
from ..gluon.block import _TraceCtx, _trace_state
from ..ndarray import NDArray
from ..telemetry import catalog as _cat
from ..telemetry import costs as _costs
from ..telemetry import metrics as _met
from ..telemetry import tracing as _tr

__all__ = ["ShardedTrainer", "sharding_rules"]

#: the ``jax.named_scope`` around the optimizer update of every step
#: variant (plain, guarded, scan, ZeRO): in the compiled step's HLO the
#: whole optimizer path reads ``op_name=".../optim/..."``.
OPTIM_SCOPE = "optim"


def _gput(arr, sharding):
    """device_put that also works on MULTI-PROCESS meshes: a committed
    jax.Array cannot be re-placed onto a sharding that spans other
    processes' devices (jax rejects non-addressable targets for device
    arrays), so detour through host numpy — jax's multi-process
    device_put path accepts host arrays and verifies cross-process
    consistency. Init/feed paths only; nothing moves inside the jitted
    step."""
    if isinstance(arr, jax.Array) and not sharding.is_fully_addressable:
        arr = _np.asarray(arr)
    return jax.device_put(arr, sharding)


def _rows(datas, scan_over_batch=False):
    """Rows of one step's batch, 0 where the first data array has no
    shape. With a batch per step of a ``step_scan`` call the leading axis
    is the scan axis and the rows come second."""
    shape = getattr(datas[0], "shape", None) if datas else None
    if not shape:
        return 0
    return int(shape[1] if scan_over_batch and len(shape) > 1 else shape[0])


def _stochastic_round(x32, dtype, key):
    """Stochastically round float32 -> bfloat16 (unbiased: E[out] == x).

    Adds uniform noise over the 16 truncated mantissa bits, then
    truncates — the standard trick that lets bf16-STORED weights train
    like fp32 masters: per-step updates smaller than one bf16 ulp still
    move the weight in expectation instead of vanishing to
    round-to-nearest. (Reference keeps fp16 training unbiased the other
    way round, with fp32 master copies: src/operator/optimizer_op.cc
    mp_sgd_update.)"""
    assert jnp.dtype(dtype) == jnp.bfloat16, "SR implemented for bf16 only"
    bits = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, x32.shape, dtype=jnp.uint32) \
        & jnp.uint32(0xFFFF)
    bits = (bits + noise) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(dtype)


def sharding_rules(rules):
    """Compile [(regex, PartitionSpec), ...] into a matcher; first match wins."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def match(name):
        for prog, spec in compiled:
            if prog.search(name):
                return spec
        return P()
    return match


class ShardedTrainer:
    """Compile a gluon HybridBlock's full train step over a device mesh.

    Parameters
    ----------
    block : HybridBlock (initialized; run one forward to materialize shapes)
    loss : gluon loss Block, or callable(outputs, label) -> scalar-able array
    mesh : jax.sharding.Mesh
    rules : list of (regex, PartitionSpec) for parameter sharding (tp/ep);
        unmatched params are replicated (pure dp).
    data_specs : PartitionSpec(s) for the data batch (default: shard batch
        axis over 'dp' if present in the mesh).
    optimizer : 'sgd' | 'adam' | 'adamw'
    zero1 : shard optimizer state over the dp axis (ZeRO stage 1). Grads
        are constrained to a dp-sharded layout so GSPMD lowers the dp
        gradient reduction to REDUCE-SCATTER; each dp rank updates only its
        1/dp param shard with its 1/dp optimizer-state shard, and the fresh
        params are all-gathered back. Memory for optimizer state drops by
        the dp degree; collective bytes match all-reduce (RS + AG).
        Two formulations: "manual" (dp as an explicit shard_map axis with
        hand-placed psum_scatter/all_gather — the audited default, RS
        guaranteed in the HLO) and "auto" (with_sharding_constraint on
        grads/opt-state/params — composes with a PipelineStack's inner pp
        shard_map, which cannot nest under a manual dp region). In auto
        the partitioner may emit reduce-scatter directly or the
        pre-canonicalized all-reduce + dynamic-slice form (what the CPU
        virtual mesh shows); either way the update and optimizer state
        run on 1/dp shards. True picks manual, or auto when the model
        carries a live pipeline axis; pass the string to force one.
    grad_accum : number of microbatches to accumulate per step. The batch's
        leading dim splits into `grad_accum` slices consumed by a lax.scan;
        the optimizer applies once on the mean gradient.
    """

    def __init__(self, block, loss, mesh, rules=None, optimizer="sgd",
                 optimizer_params=None, data_specs=None, label_spec=None,
                 dp_axis="dp", compute_dtype=None, zero1=False, grad_accum=1,
                 opt_state_dtype=None, param_dtype=None):
        self._block = block
        self._loss = loss
        self._mesh = mesh
        self._opt = optimizer
        # mixed precision: fp32 master weights + optimizer state, compute in
        # compute_dtype (reference: mp_sgd_update fp16 master-weight ops,
        # src/operator/optimizer_op.cc) — on TPU bfloat16 feeds the MXU at
        # full rate with no loss-scaling needed.
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype is not None else None)
        # low-precision optimizer state (bf16 moments): halves the Adam
        # m/v HBM traffic — the dominant non-activation term of a large
        # model's step (BENCHMARKS.md BERT roofline). Update math still
        # runs in fp32; only the STORED moments round. Master weights
        # stay fp32 regardless.
        self._opt_state_dtype = (jnp.dtype(opt_state_dtype)
                                 if opt_state_dtype is not None else None)
        # bf16-STORED parameters with stochastic-rounding write-back: no
        # fp32 master copy at all — halves the weight read+write HBM
        # traffic the BERT roofline names as the largest remaining
        # non-activation term. Update math still runs fp32; the rounding
        # is unbiased (see _stochastic_round), so sub-ulp updates
        # accumulate in expectation. Aux (BN running stats) stay fp32.
        self._param_dtype = (jnp.dtype(param_dtype)
                             if param_dtype is not None else None)
        if self._param_dtype is not None and \
                self._param_dtype != jnp.bfloat16:
            raise ValueError("param_dtype supports bfloat16 only")
        if self._param_dtype is not None and self._compute_dtype is None:
            # bf16-stored weights imply bf16 compute (the data batch must
            # match the weights' dtype inside convs/matmuls)
            self._compute_dtype = self._param_dtype
        hp = dict(optimizer_params or {})
        self._lr = float(hp.get("learning_rate", 0.01))
        self._momentum = float(hp.get("momentum", 0.0))
        self._wd = float(hp.get("wd", 0.0))
        self._beta1 = float(hp.get("beta1", 0.9))
        self._beta2 = float(hp.get("beta2", 0.999))
        self._eps = float(hp.get("epsilon", 1e-8))
        self._step_count = 0

        params = {p.name: p for p in block.collect_params().values()}
        self._params_ref = params
        self._diff_names = sorted(n for n, p in params.items()
                                  if p.grad_req != "null" and p._data is not None)
        self._aux_names = sorted(n for n, p in params.items()
                                 if p.grad_req == "null" and p._data is not None)
        matcher = sharding_rules(rules or [])
        self._param_shardings = {n: NamedSharding(mesh, matcher(n))
                                 for n in self._diff_names + self._aux_names}
        pdt = self._param_dtype

        def _stored(n):
            arr = params[n]._data._data
            if pdt is not None and n in self._diff_names and \
                    jnp.issubdtype(arr.dtype, jnp.floating):
                arr = arr.astype(pdt)
            return _gput(arr, self._param_shardings[n])

        self._param_vals = {n: _stored(n)
                            for n in self._diff_names + self._aux_names}
        self._dp_axis = dp_axis
        self._dp_size = dict(mesh.shape).get(dp_axis, 1)
        if zero1 not in (False, True, "manual", "auto"):
            raise ValueError("zero1 must be False/True/'manual'/'auto', "
                             "got %r" % (zero1,))
        live_pp = [a for a in self._pipeline_axes(block)
                   if dict(mesh.shape).get(a, 1) > 1]
        if zero1 and self._dp_size > 1:
            if zero1 is True:
                # the manual formulation's dp shard_map cannot nest over a
                # PipelineStack's inner pp shard_map (Shardy rejects
                # re-binding an already-manual mesh); auto-select the
                # constraint formulation there
                self._zero1_mode = "auto" if live_pp else "manual"
            else:
                self._zero1_mode = zero1
        else:
            self._zero1_mode = None
        self._zero1 = self._zero1_mode == "manual"
        if self._zero1 and live_pp:
            raise NotImplementedError(
                "zero1='manual' cannot compose with pipeline axis %r in "
                "one step; use zero1='auto' (with_sharding_constraint "
                "formulation) with pipeline parallelism" % live_pp[0])
        self._accum = int(grad_accum)
        if self._accum < 1:
            raise ValueError("grad_accum must be >= 1")
        if self._zero1_mode:
            self._zero_axes = {n: self._zero_axis_for(n)
                               for n in self._diff_names}
            self._zero_shardings = {n: self._zero_sharding(n)
                                    for n in self._diff_names}
        else:
            self._zero_axes, self._zero_shardings = {}, {}
        self._opt_state = self._init_opt_state()

        dp_in_mesh = dp_axis in mesh.axis_names
        default_spec = P(dp_axis) if dp_in_mesh else P()
        if data_specs is None:
            data_specs = default_spec
        # a bare PartitionSpec is a tuple subclass on some jax versions:
        # it means ONE spec for every data array, not a per-array list
        if isinstance(data_specs, (list, tuple)) \
                and not isinstance(data_specs, P):
            self._data_shardings = [NamedSharding(mesh, s) for s in data_specs]
        else:
            self._data_shardings = NamedSharding(mesh, data_specs)
        self._label_sharding = NamedSharding(
            mesh, label_spec if label_spec is not None else default_spec)
        self._jit_step = None
        self._jit_step_guarded = None
        self._step_is_aot = False
        # AOT plumbing: serialized executables handed in by
        # load_executables (checkpoint `executables` section) keyed by
        # program name, and the compiled programs this trainer built
        # (the export_executables source)
        self._imported_exes = {}
        self._aot_built = {}
        self._telemetry_labels = {"zero": self._zero1_mode or "off",
                                  "pipeline": "on" if live_pp else "off"}
        _cat.install_jax_compile_hook()

    @staticmethod
    def _pipeline_axes(block):
        """Mesh axis names claimed by PipelineStack children of `block`."""
        from .pipeline import PipelineStack
        axes = set()

        def walk(b):
            if isinstance(b, PipelineStack):
                axes.add(b._pp_axis)
            for child in getattr(b, "_children", {}).values():
                walk(child)
        walk(block)
        return axes

    # ------------------------------------------------------------------ opt
    def _zero_axis_for(self, n):
        """ZeRO-1 shard dimension for param n: the first free dimension the
        dp degree divides (its spec entry is None so tp/ep shardings stay
        untouched). None = no such dimension; that param keeps replicated
        optimizer state (tiny biases — negligible memory)."""
        shape = self._param_vals[n].shape
        spec = tuple(self._param_shardings[n].spec)
        spec = spec + (None,) * (len(shape) - len(spec))
        for i, (dim, ax) in enumerate(zip(shape, spec)):
            if ax is None and dim % self._dp_size == 0 and dim > 0:
                return i
        return None

    def _zero_sharding(self, n):
        """NamedSharding for param n's ZeRO-1 optimizer-state storage
        (shard axis single-sourced from self._zero_axes)."""
        i = self._zero_axes[n]
        if i is None:
            return self._param_shardings[n]
        spec = tuple(self._param_shardings[n].spec)
        spec = spec + (None,) * (self._param_vals[n].ndim - len(spec))
        return NamedSharding(
            self._mesh, P(*spec[:i], self._dp_axis, *spec[i + 1:]))

    def _init_opt_state(self):
        state = {}
        if self._opt == "sgd" and self._momentum == 0.0:
            return state
        # bf16-stored params do NOT imply bf16 opt state: unless the user
        # asked for low-precision state explicitly, slots stay fp32
        # (state has no SR; nearest-rounded bf16 state is a separate,
        # opt-in precision decision)
        fallback = (jnp.float32 if self._param_dtype is not None else None)
        for n in self._diff_names:
            sh = self._zero_shardings.get(n, self._param_shardings[n])
            ref = self._param_vals[n]
            sdt = self._opt_state_dtype or fallback or ref.dtype
            z = _gput(jnp.zeros(ref.shape, sdt), sh)
            if self._opt == "sgd":
                state[n] = (z,)
            else:
                state[n] = (z, _gput(jnp.zeros(ref.shape, sdt), sh))
        return state

    def _apply_opt(self, p, g, st, t, key=None):
        # bf16-stored params: lift to fp32 for the update math, write back
        # with unbiased stochastic rounding (or nearest if no key given)
        sr = (self._param_dtype is not None and p.dtype == self._param_dtype)
        if sr:
            pdt = p.dtype
            p = p.astype(jnp.float32)
            g = g.astype(jnp.float32)
        newp, new_st = self._apply_opt_fp(p, g, st, t)
        if sr:
            newp = (_stochastic_round(newp, pdt, key) if key is not None
                    else newp.astype(pdt))
        return newp, new_st

    def _apply_opt_fp(self, p, g, st, t):
        lr, wd = self._lr, self._wd
        if self._opt == "sgd":
            if self._momentum == 0.0:
                return p - lr * (g + wd * p), st
            (mom,) = st
            sdt = mom.dtype
            mom = (self._momentum * mom.astype(p.dtype)
                   - lr * (g + wd * p))
            return p + mom, (mom.astype(sdt),)
        if self._opt in ("adam", "adamw"):
            m, v = st
            sdt = m.dtype
            if sdt != p.dtype:                 # low-precision stored state:
                m = m.astype(p.dtype)          # math in master precision,
                v = v.astype(p.dtype)          # storage rounds on the way out
            if self._opt == "adam":
                g = g + wd * p
            m = self._beta1 * m + (1 - self._beta1) * g
            v = self._beta2 * v + (1 - self._beta2) * g * g
            mhat = m / (1 - self._beta1 ** t)
            vhat = v / (1 - self._beta2 ** t)
            upd = lr * mhat / (jnp.sqrt(vhat) + self._eps)
            if self._opt == "adamw":
                upd = upd + lr * wd * p
            return p - upd, (m.astype(sdt), v.astype(sdt))
        raise ValueError(self._opt)

    # ----------------------------------------------------------------- step
    def _build(self, n_data_args):
        return jax.jit(self._build_raw(n_data_args), donate_argnums=(0, 1, 2))

    # ---------------------------------------------------- compile plumbing
    def _aot_wanted(self):
        """Use the AOT lower+compile path (a pinned jax.stages.Compiled)
        instead of plain jax.jit: opted in by the persistent compile
        cache, by imported serialized executables, or by MXTPU_COSTS=1 —
        cost capture needs the compiled object anyway, and routing it
        through one shared lower+compile is what removes the old
        second non-donating compile."""
        return (_ccstore.enabled() or bool(self._imported_exes)
                or _costs.capture_enabled())

    def _exe_args(self, datas, labels, key):
        """The step calling convention at its current avals (lowering
        only — nothing executes)."""
        pv = {n: self._param_vals[n] for n in self._diff_names}
        av = {n: self._param_vals[n] for n in self._aux_names}
        return (pv, av, self._opt_state, jnp.float32(1), key,
                *datas, *labels)

    def _compile_program(self, exe_name, jit_fn, args, cost_name=None,
                         samples_per_exec=None):
        """Produce ONE executable for `exe_name`: bind an imported
        serialized executable when a checkpoint shipped one, else
        lower+compile through the persistent cache. Cost capture
        (MXTPU_COSTS=1) reads the SAME executable — no extra compile."""
        blob = self._imported_exes.pop(exe_name, None)
        compiled = None
        if blob is not None:
            try:
                compiled = _aot.deserialize_compiled(blob)
                _cat.aot_executables_imported.inc(where="trainer")
            except Exception as e:  # noqa: BLE001 — a blob from another
                # backend/jaxlib must fall back to compiling, never crash
                import warnings
                warnings.warn("trainer: imported executable %r failed to "
                              "deserialize (%s: %s); recompiling"
                              % (exe_name, type(e).__name__, e))
                compiled, blob = None, None
        if compiled is None:
            lowered = jit_fn.lower(*args)
            compiled, blob = _aot.cached_compile(
                lowered, name="trainer." + exe_name, where="trainer",
                mesh=self._mesh, donation=(0, 1, 2), want_blob=True)
        # keep the blob the executable was loaded from / published as:
        # a deserialized executable cannot re-serialize, so this is the
        # only durable form export_executables can ship
        self._aot_built[exe_name] = (compiled, blob)
        if cost_name is not None:
            _aot.capture_cost(cost_name, compiled,
                              samples_per_exec=samples_per_exec)
        return compiled

    def _ensure_step_program(self, datas, labels, key):
        """Build self._jit_step for this batch signature (AOT path when
        opted in, plain jax.jit otherwise)."""
        if self._jit_step is not None:
            return
        if self._aot_wanted():
            batch = (int(datas[0].shape[0])
                     if datas and getattr(datas[0], "shape", None) else None)
            self._jit_step = self._compile_program(
                "step", self._build(len(datas)),
                self._exe_args(datas, labels, key),
                cost_name="trainer.step", samples_per_exec=batch)
            self._step_is_aot = True
        else:
            self._jit_step = self._build(len(datas))
            self._step_is_aot = False

    def precompile(self, data, label, key=None):
        """Warmup hook: compile (or cache-hit / import) the step program
        for this batch signature WITHOUT consuming the batch or mutating
        training state. Returns self."""
        datas, labels = self._prep_batch(data, label)
        if key is None:
            key = jax.random.PRNGKey(0)
        self._ensure_step_program(datas, labels, key)
        return self

    def export_executables(self):
        """{program_name: blob} of every AOT-compiled program this
        trainer holds, serialized for a checkpoint's ``executables``
        section. Empty when the AOT path never engaged (cache off and no
        MXTPU_COSTS) or the backend cannot serialize executables."""
        out = {}
        for exe_name, (compiled, blob) in self._aot_built.items():
            if blob is not None:
                out[exe_name] = blob
                continue
            try:
                out[exe_name] = _aot.serialize_compiled(compiled)
            except Exception:  # noqa: BLE001 — backends without
                continue       # executable serialization export nothing
        return out

    def load_executables(self, blobs):
        """Accept serialized executables restored from a checkpoint
        (CheckpointManager.load_executables). Each binds lazily the
        first time its program is needed; an incompatible blob falls
        back to a fresh compile."""
        if blobs:
            self._imported_exes.update(blobs)
        return self

    def _make_grad_stage(self, n_data_args):
        """Shared loss/grad computation: returns grads(param_vals, aux_vals,
        data, label, key) -> (grads, new_aux, loss), with the grad-accum
        microbatch scan folded in. Under zero1 this runs PER dp RANK (batch
        = the rank's local slice) inside the manual region."""
        block, loss_block = self._block, self._loss
        aux_names = self._aux_names
        cdt = self._compute_dtype
        accum = self._accum

        def loss_fn(pv, av, data, label, key, scale=None):
            if cdt is not None:
                data = tuple(d.astype(cdt)
                             if jnp.issubdtype(d.dtype, jnp.floating)
                             else d for d in data)
                pv_c = {n: (v.astype(cdt) if jnp.issubdtype(v.dtype, jnp.floating)
                            else v) for n, v in pv.items()}
                aux_c = {n: (v.astype(cdt) if jnp.issubdtype(v.dtype, jnp.floating)
                             else v) for n, v in av.items()}
            else:
                pv_c, aux_c = pv, av
            ctx = _TraceCtx({**pv_c, **aux_c}, key, training=True,
                            mesh_ctx=self._mesh)
            prev = getattr(_trace_state, "ctx", None)
            _trace_state.ctx = ctx
            try:
                out = block.forward(*data)
                loss = loss_block(out, *label)
                loss = jnp.mean(loss.astype(jnp.float32))
                if scale is not None:
                    # dynamic loss scaling (step_guarded): multiply INSIDE
                    # the differentiated function so the backward pass runs
                    # on the scaled loss
                    loss = loss * scale
            finally:
                _trace_state.ctx = prev
            new_aux = {n: ctx.aux_updates.get(n, av[n]) for n in aux_names}
            if cdt is not None:   # running stats stay fp32 master copies
                new_aux = {n: v.astype(av[n].dtype)
                           for n, v in new_aux.items()}
            return loss, new_aux

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def grads_of(param_vals, aux_vals, data, label, key, scale=None):
            if accum == 1:
                (loss, new_aux), grads = grad_fn(param_vals, aux_vals, data,
                                                 label, key, scale)
            else:
                # microbatch scan: split the batch's leading dim and average
                # the gradients — the optimizer (and its collective traffic
                # under zero1) runs ONCE per step, not per micro
                mb = tuple(a.reshape((accum, a.shape[0] // accum)
                                     + a.shape[1:])
                           for a in data + label)
                keys = jax.random.split(key, accum)

                def body(carry, xs):
                    g_sum, aux_c, loss_sum = carry
                    k_i, arrs = xs[0], xs[1:]
                    (loss, new_aux), g = grad_fn(param_vals, aux_c,
                                                 arrs[:len(data)],
                                                 arrs[len(data):], k_i,
                                                 scale)
                    g_sum = jax.tree_util.tree_map(jnp.add, g_sum, g)
                    return (g_sum, new_aux, loss_sum + loss), None

                # accumulate in fp32 even when params are stored bf16 —
                # microbatch contributions below one bf16 ulp must not
                # vanish
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape,
                                        jnp.float32 if jnp.issubdtype(
                                            p.dtype, jnp.floating)
                                        else p.dtype),
                    param_vals)
                (grads, new_aux, loss), _ = jax.lax.scan(
                    body, (g0, aux_vals, jnp.float32(0)), (keys,) + mb)
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                loss = loss / accum
            if scale is not None:
                # undo the loss scale on the way out: callers always see
                # the TRUE loss/grads; an overflowed backward still shows
                # up as inf/nan (that is the detection signal)
                inv = 1.0 / scale
                grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
                loss = loss * inv
            return grads, new_aux, loss

        return grads_of

    def _apply_all(self, param_vals, grads, opt_state, t, upd_key):
        """Apply the optimizer to every differentiable param — the shared
        update stage of the plain and guarded step builders. Handles the
        auto-ZeRO-1 with_sharding_constraint formulation; `upd_key` is the
        stochastic-rounding key base (None for fp32-stored params)."""
        auto_zero = self._zero1_mode == "auto"
        new_params, new_opt = {}, {}
        for i, n in enumerate(self._diff_names):
            k_n = (jax.random.fold_in(upd_key, i)
                   if upd_key is not None else None)
            st = opt_state.get(n, ())
            p, g = param_vals[n], grads[n]
            if auto_zero and self._zero_axes[n] is not None:
                # ZeRO-1, constraint formulation: pin the grad, the
                # param copy the optimizer reads, and the opt state to
                # the dp-sharded layout — GSPMD lowers the dp grad
                # reduction to reduce-scatter, runs the update on 1/dp
                # shards, and all-gathers the fresh params back to the
                # replicated layout pinned on the output
                zsh = self._zero_shardings[n]
                g = jax.lax.with_sharding_constraint(g, zsh)
                p = jax.lax.with_sharding_constraint(p, zsh)
                st = tuple(jax.lax.with_sharding_constraint(s, zsh)
                           for s in st)
                newp, new_st = self._apply_opt(p, g, st, t, key=k_n)
                newp = jax.lax.with_sharding_constraint(
                    newp, self._param_shardings[n])
            else:
                newp, new_st = self._apply_opt(p, g, st, t, key=k_n)
            new_params[n] = newp
            if new_st:
                new_opt[n] = new_st
        return new_params, new_opt

    def _build_raw(self, n_data_args):
        if self._zero1:
            return self._build_raw_zero1(n_data_args)
        grads_of = self._make_grad_stage(n_data_args)

        def step_fn(param_vals, aux_vals, opt_state, t, key, *batch):
            data, label = batch[:n_data_args], batch[n_data_args:]
            grads, new_aux, loss = grads_of(param_vals, aux_vals, data,
                                            label, key)
            # decorrelated key stream for stochastic-rounding write-back
            upd_key = (jax.random.fold_in(key, 0x51A57)
                       if self._param_dtype is not None else None)
            with jax.named_scope(OPTIM_SCOPE):
                new_params, new_opt = self._apply_all(param_vals, grads,
                                                      opt_state, t, upd_key)
            return new_params, new_aux, new_opt, loss

        return step_fn

    def _build_raw_guarded(self, n_data_args):
        """Numeric-guarded step (resilience.GuardedTrainer): compute grads
        under a loss scale, check loss/grad-norm finiteness ON DEVICE, and
        select between updated and previous state with jnp.where — a
        skipped step runs the same XLA program (no retrace, composes with
        donation), and the host learns the verdict from ONE fused scalar
        read of the stats vector."""
        if self._zero1:
            raise NotImplementedError(
                "step_guarded does not support zero1='manual': the global "
                "grad norm lives inside the manual dp shard_map region; "
                "use zero1='auto' with the numeric guard")
        grads_of = self._make_grad_stage(n_data_args)

        def step_fn(param_vals, aux_vals, opt_state, t, key, scale, *batch):
            data, label = batch[:n_data_args], batch[n_data_args:]
            grads, new_aux, loss = grads_of(param_vals, aux_vals, data,
                                            label, key, scale)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in grads.values()))
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            upd_key = (jax.random.fold_in(key, 0x51A57)
                       if self._param_dtype is not None else None)
            with jax.named_scope(OPTIM_SCOPE):
                new_params, new_opt = self._apply_all(param_vals, grads,
                                                      opt_state, t, upd_key)

            # skip-step: elementwise select old vs new (both sides already
            # computed). where, not cond: a NaN in the rejected branch
            # never reaches the selected values, and select keeps the
            # donation aliasing of the plain step
            def sel(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), new, old)
            new_params = sel(new_params,
                             {n: param_vals[n] for n in new_params})
            new_aux = sel(new_aux, {n: aux_vals[n] for n in new_aux})
            if new_opt:
                new_opt = sel(new_opt, {n: opt_state[n] for n in new_opt})
            stats = jnp.stack([1.0 - ok.astype(jnp.float32), gnorm,
                               loss.astype(jnp.float32)])
            return new_params, new_aux, new_opt, loss, stats

        return step_fn

    def _manual_spec(self, sharding):
        """Project a NamedSharding's spec onto the dp axis only (shard_map
        in_specs may reference manual axes only; tp/sp/... stay auto)."""
        spec = tuple(sharding.spec)
        return P(*((ax if ax == self._dp_axis else None) for ax in spec))

    def _build_raw_zero1(self, n_data_args):
        """ZeRO-1 step: dp is a MANUAL shard_map axis with explicit
        collectives — psum_scatter(grad) -> shard-local optimizer ->
        all_gather(params) — while tp/sp/... stay GSPMD-auto. This is the
        reduce-scatter formulation of data parallelism (same bytes as
        all-reduce, 1/dp optimizer memory); the KVStore-device superset per
        SURVEY §2.4. Note: batch stats (BatchNorm aux) are computed per dp
        rank and pmean'd — the reference's per-device BN semantics."""
        diff_names = self._diff_names
        dp, dp_size = self._dp_axis, self._dp_size
        grads_of = self._make_grad_stage(n_data_args)
        zero_axes = self._zero_axes

        def manual_step(param_vals, aux_vals, opt_state, t, key, *batch):
            data, label = batch[:n_data_args], batch[n_data_args:]
            # SR keys must derive from the PRE-rank-fold key: replicated
            # (ax-is-None) params apply identical rounding noise on every
            # rank, keeping the replicas bit-identical
            upd_key = (jax.random.fold_in(key, 0x51A57)
                       if self._param_dtype is not None else None)
            # per-rank dropout/noise streams
            key = jax.random.fold_in(key, jax.lax.axis_index(dp))
            grads, new_aux, loss = grads_of(param_vals, aux_vals, data,
                                            label, key)
            loss = jax.lax.pmean(loss, dp)
            new_aux = {n: (jax.lax.pmean(v, dp)
                           if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                       for n, v in new_aux.items()}
            new_params, new_opt = {}, {}
            with jax.named_scope(OPTIM_SCOPE):
                for i, n in enumerate(diff_names):
                    k_n = (jax.random.fold_in(upd_key, i)
                           if upd_key is not None else None)
                    st = opt_state.get(n, ())
                    p, g = param_vals[n], grads[n]
                    ax = zero_axes[n]
                    if ax is None:
                        # no dp-divisible dim: plain all-reduce, full update
                        g = jax.lax.pmean(g, dp)
                        newp, new_st = self._apply_opt(p, g, st, t, key=k_n)
                    else:
                        # grad mean arrives SHARDED (reduce-scatter), each
                        # rank updates only its 1/dp slice of param + opt
                        # state, fresh weights are all-gathered
                        g = jax.lax.psum_scatter(
                            g, dp, scatter_dimension=ax,
                            tiled=True) / dp_size
                        size = p.shape[ax] // dp_size
                        start = jax.lax.axis_index(dp) * size
                        p_sh = jax.lax.dynamic_slice_in_dim(p, start, size,
                                                            axis=ax)
                        newp_sh, new_st = self._apply_opt(p_sh, g, st, t,
                                                          key=k_n)
                        newp = jax.lax.all_gather(newp_sh, dp, axis=ax,
                                                  tiled=True)
                    new_params[n] = newp
                    if new_st:
                        new_opt[n] = new_st
            return new_params, new_aux, new_opt, loss

        rep = P()
        param_specs = {n: rep for n in diff_names}
        aux_specs = {n: rep for n in self._aux_names}
        opt_specs = {n: tuple(self._manual_spec(self._zero_shardings[n])
                              for _ in st)
                     for n, st in self._opt_state.items()}
        if isinstance(self._data_shardings, list):
            data_specs = tuple(self._manual_spec(s)
                               for s in self._data_shardings)
        else:
            data_specs = (self._manual_spec(self._data_shardings),) \
                * n_data_args
        label_manual = self._manual_spec(self._label_sharding)

        def step_fn(param_vals, aux_vals, opt_state, t, key, *batch):
            n_labels = len(batch) - n_data_args
            in_specs = (param_specs, aux_specs,
                        {n: opt_specs[n] for n in opt_state},
                        rep, rep) + data_specs[:n_data_args] \
                + (label_manual,) * n_labels
            out_specs = (param_specs,
                         {n: rep for n in aux_vals},
                         {n: opt_specs[n] for n in opt_state},
                         rep)
            return jax.shard_map(
                manual_step, mesh=self._mesh, in_specs=in_specs,
                out_specs=out_specs, axis_names={dp}, check_vma=False,
            )(param_vals, aux_vals, opt_state, t, key, *batch)

        return step_fn

    def _build_scan(self, n_data_args, n_steps, scan_over_batch):
        """K train steps in ONE XLA program via lax.scan — removes the
        per-step host dispatch gap and lets XLA overlap the optimizer tail with the next
        forward. Batch handling: scan_over_batch=True consumes a leading
        steps-axis (fresh batch per step); False reuses one resident batch."""
        step_fn = self._build_raw(n_data_args)

        def scan_fn(param_vals, aux_vals, opt_state, t0, key, *batch):
            keys = jax.random.split(key, n_steps)
            if scan_over_batch:
                def body(carry, xs):
                    pv, av, st, t = carry
                    pv, av, st, loss = step_fn(pv, av, st, t, xs[0], *xs[1:])
                    return (pv, av, st, t + 1.0), loss
                xs = (keys,) + tuple(batch)
            else:
                def body(carry, k):
                    pv, av, st, t = carry
                    pv, av, st, loss = step_fn(pv, av, st, t, k, *batch)
                    return (pv, av, st, t + 1.0), loss
                xs = keys
            (pv, av, st, _), losses = jax.lax.scan(
                body, (param_vals, aux_vals, opt_state, t0), xs)
            return pv, av, st, losses

        return jax.jit(scan_fn, donate_argnums=(0, 1, 2))

    def step_scan(self, data, label, n_steps, key=None, per_step_batches=None):
        """Run `n_steps` train steps as one compiled program.

        per_step_batches=True: every data/label array carries a leading axis
        of length `n_steps` and one slice is consumed per step. False: the
        same resident batch is reused every step (single-batch overfit /
        benchmarking). None (default): inferred — True iff every array's
        leading dim equals `n_steps` (ambiguous when the batch size equals
        `n_steps`; pass the flag explicitly in that case). Returns the
        per-step loss array (device-resident).
        """
        with _tr.span("trainer.step") as sp:
            with _tr.span("trainer.prep_batch"):
                datas, labels, scan_over_batch = self._prep_scan_batch(
                    data, label, n_steps, per_step_batches)
            sp.set_attr("rows", _rows(datas, scan_over_batch))
            with _tr.span("trainer.dispatch"):
                new_params, new_aux, new_opt, losses = self._dispatch_scan(
                    datas, labels, n_steps, scan_over_batch, key)
            self._param_vals = {**new_params, **new_aux}
            self._opt_state = new_opt if new_opt else self._opt_state
        return losses

    def _prep_scan_batch(self, data, label, n_steps, per_step_batches):
        """Place the batch of a ``step_scan`` call.
        -> (datas, labels, scan_over_batch)"""
        datas = list(data) if isinstance(data, (list, tuple)) else [data]
        labels = list(label) if isinstance(label, (list, tuple)) else [label]
        datas = [d._data if isinstance(d, NDArray) else jnp.asarray(d)
                 for d in datas]
        labels = [l._data if isinstance(l, NDArray) else jnp.asarray(l)
                  for l in labels]
        if per_step_batches is None:
            per_step_batches = all(a.shape[:1] == (n_steps,)
                                   for a in datas + labels) and n_steps > 1
        scan_over_batch = per_step_batches

        def _shard(spec_sharding):
            # in per-step-batch mode the leading axis is the scan (steps)
            # axis: keep it unsharded, shift the user's spec right by one
            if not scan_over_batch:
                return spec_sharding
            return NamedSharding(self._mesh,
                                 P(None, *spec_sharding.spec))
        if isinstance(self._data_shardings, list):
            if len(self._data_shardings) != len(datas):
                raise ValueError("data_specs has %d entries but step_scan got "
                                 "%d data arrays" % (len(self._data_shardings),
                                                     len(datas)))
            datas = [_gput(d, _shard(s))
                     for d, s in zip(datas, self._data_shardings)]
        else:
            datas = [_gput(d, _shard(self._data_shardings))
                     for d in datas]
        labels = [_gput(l, _shard(self._label_sharding))
                  for l in labels]
        return datas, labels, scan_over_batch

    def _dispatch_scan(self, datas, labels, n_steps, scan_over_batch, key):
        """The host's part of a ``step_scan`` call after the batch is
        placed: key and step scalars, the program (built once for a
        shape), the call, the retry. -> the program's outputs"""
        cache_key = (len(datas), n_steps, scan_over_batch)
        if getattr(self, "_scan_cache", None) is None:
            self._scan_cache = {}
        if key is None:
            key = jax.random.PRNGKey(self._step_count)
        t = jnp.float32(self._step_count + 1)
        self._step_count += n_steps
        pv = {n: self._param_vals[n] for n in self._diff_names}
        aux_vals = {n: self._param_vals[n] for n in self._aux_names}
        scan_args = (pv, aux_vals, self._opt_state, t, key,
                     *(datas + labels))

        def _build_scan_program():
            jit_fn = self._build_scan(len(datas), n_steps, scan_over_batch)
            if not self._aot_wanted():
                return jit_fn, False
            # AOT path: ONE lower+compile through the persistent cache
            # serves both execution and MXTPU_COSTS accounting (the old
            # path paid a second, non-donating compile for the latter)
            exe_name = "scan/%d_%d_%d" % (len(datas), n_steps,
                                          int(scan_over_batch))
            return self._compile_program(
                exe_name, jit_fn, scan_args, cost_name="trainer.step_scan",
                samples_per_exec=(_rows(datas, scan_over_batch)
                                  * n_steps) or None), True
        if cache_key not in self._scan_cache:
            self._scan_cache[cache_key] = _build_scan_program()
        t0 = time.perf_counter() if _met.enabled() else None
        scan_fn, scan_is_aot = self._scan_cache[cache_key]
        try:
            new_params, new_aux, new_opt, losses = scan_fn(*scan_args)
        except TypeError:
            if not scan_is_aot:
                raise
            # pinned avals drifted (new batch shape under the same cache
            # key): re-lower through the cache and retry once
            self._scan_cache[cache_key] = _build_scan_program()
            new_params, new_aux, new_opt, losses = \
                self._scan_cache[cache_key][0](*scan_args)
        if t0 is not None:
            lbl = self._telemetry_labels
            _cat.trainer_steps.inc(n_steps, **lbl)
            rows = _rows(datas, scan_over_batch)
            if rows:
                _cat.trainer_samples.inc(rows * n_steps)
            _costs.observe("trainer.step_scan", time.perf_counter() - t0)
        return new_params, new_aux, new_opt, losses

    def _prep_batch(self, data, label):
        datas = list(data) if isinstance(data, (list, tuple)) else [data]
        labels = list(label) if isinstance(label, (list, tuple)) else [label]
        datas = [d._data if isinstance(d, NDArray) else jnp.asarray(d)
                 for d in datas]
        labels = [l._data if isinstance(l, NDArray) else jnp.asarray(l)
                  for l in labels]
        if isinstance(self._data_shardings, list):
            if len(self._data_shardings) != len(datas):
                raise ValueError("data_specs has %d entries but step got %d "
                                 "data arrays" % (len(self._data_shardings),
                                                  len(datas)))
            datas = [_gput(d, s)
                     for d, s in zip(datas, self._data_shardings)]
        else:
            datas = [_gput(d, self._data_shardings) for d in datas]
        labels = [_gput(l, self._label_sharding) for l in labels]
        return datas, labels

    def place_batch(self, data, label):
        """Device-place one (data, label) batch exactly as ``step``
        would — public so prefetch threads (StreamLoader / pin_memory)
        can pay the host→device transfer ahead of the step; ``step``
        then re-places already-resident arrays for free."""
        datas, labels = self._prep_batch(data, label)
        return (datas[0] if len(datas) == 1 else datas,
                labels[0] if len(labels) == 1 else labels)

    def stream_loader(self, coordinator=None, data_keys=("data",),
                      label_keys=("label",), epochs=1, start_epoch=0,
                      depth=None, retry_window=None, client=None):
        """A stream-plane loader feeding this trainer: yields device-
        placed ``(data, label)`` pairs whose transfer (sharded
        device_put) ran on the prefetch thread, overlapping the
        in-flight step. ``data_keys``/``label_keys`` pick arrays out of
        each batch dict in ``step``'s argument order."""
        from ..io.stream.loader import StreamLoader

        def _transfer(batch):
            data = [batch[k] for k in data_keys]
            label = [batch[k] for k in label_keys]
            return self.place_batch(data, label)

        return StreamLoader(coordinator=coordinator, client=client,
                            epochs=epochs, start_epoch=start_epoch,
                            depth=depth, transfer=_transfer,
                            retry_window=retry_window)

    def _dispatch_step(self, datas, labels, key):
        """The host's part of one step after the batch is placed: the key
        and step scalars, the jitted call, the retrace retry."""
        if key is None:
            key = jax.random.PRNGKey(self._step_count)
        self._ensure_step_program(datas, labels, key)
        self._step_count += 1
        t = jnp.float32(self._step_count)
        self._param_vals_diff = {n: self._param_vals[n] for n in self._diff_names}
        aux_vals = {n: self._param_vals[n] for n in self._aux_names}
        try:
            return self._jit_step(
                self._param_vals_diff, aux_vals, self._opt_state, t, key,
                *datas, *labels)
        except TypeError:
            if not self._step_is_aot:
                raise
            # an AOT executable is pinned to its compile-time avals: a
            # changed batch signature (where plain jit would retrace)
            # re-lowers through the cache and retries once
            self._jit_step = None
            self._ensure_step_program(datas, labels, key)
            return self._jit_step(
                self._param_vals_diff, aux_vals, self._opt_state, t, key,
                *datas, *labels)

    def step(self, data, label, key=None):
        """Run one sharded train step; returns the (device) scalar loss."""
        t0 = time.perf_counter() if _met.enabled() else None
        with _tr.span("trainer.step") as sp:
            with _tr.span("trainer.prep_batch"):
                datas, labels = self._prep_batch(data, label)
            rows = _rows(datas)
            sp.set_attr("rows", rows)
            with _tr.span("trainer.dispatch"):
                new_params, new_aux, new_opt, loss = self._dispatch_step(
                    datas, labels, key)
            self._param_vals = {**new_params, **new_aux}
            self._opt_state = new_opt if new_opt else self._opt_state
        if t0 is not None:
            dt = time.perf_counter() - t0
            lbl = self._telemetry_labels
            _cat.trainer_step_seconds.observe(dt, **lbl)
            _cat.trainer_steps.inc(**lbl)
            if rows:
                _cat.trainer_samples.inc(rows)
            _costs.observe("trainer.step", dt)
        return loss

    def step_guarded(self, data, label, loss_scale=1.0, key=None):
        """One numeric-guarded train step (resilience.GuardedTrainer's
        primitive). Returns ``(loss, notfinite, grad_norm)``:

        - loss : device scalar, UNSCALED true loss (may be nan/inf when
          the step was bad);
        - notfinite : host bool — True means loss or global grad norm was
          non-finite and the update was SKIPPED on-device (params, aux
          and optimizer state unchanged);
        - grad_norm : host float global L2 grad norm (inf/nan on a bad
          step).

        `loss_scale` multiplies the loss inside the backward (dynamic
        loss scaling); grads and the returned loss are unscaled. Passed
        as a traced jnp scalar, so changing it never retraces. Costs one
        fused 3-float device->host read vs step().
        """
        t0 = time.perf_counter() if _met.enabled() else None
        with _tr.span("trainer.step") as sp:
            with _tr.span("trainer.prep_batch"):
                datas, labels = self._prep_batch(data, label)
            rows = _rows(datas)
            sp.set_attr("rows", rows)
            with _tr.span("trainer.dispatch"):
                if self._jit_step_guarded is None:
                    self._jit_step_guarded = jax.jit(
                        self._build_raw_guarded(len(datas)),
                        donate_argnums=(0, 1, 2))
                if key is None:
                    key = jax.random.PRNGKey(self._step_count)
                self._step_count += 1
                t = jnp.float32(self._step_count)
                pv = {n: self._param_vals[n] for n in self._diff_names}
                aux_vals = {n: self._param_vals[n] for n in self._aux_names}
                new_params, new_aux, new_opt, loss, stats = \
                    self._jit_step_guarded(
                        pv, aux_vals, self._opt_state, t, key,
                        jnp.float32(loss_scale), *datas, *labels)
            self._param_vals = {**new_params, **new_aux}
            self._opt_state = new_opt if new_opt else self._opt_state
            stats = jax.device_get(stats)   # the ONE host sync of the step
        if t0 is not None:
            lbl = self._telemetry_labels
            _cat.trainer_step_seconds.observe(time.perf_counter() - t0,
                                              **lbl)
            _cat.trainer_steps.inc(**lbl)
            if rows:
                _cat.trainer_samples.inc(rows)
        return loss, bool(stats[0] > 0.5), float(stats[1])

    def _inspection_step(self, data, label, key=None):
        """Shared no-donation prep: the compiled-step calling convention
        lives HERE and only here. Returns (jitted_fn, args)."""
        datas, labels = self._prep_batch(data, label)
        fn = jax.jit(self._build_raw(len(datas)))   # no donation
        if key is None:
            key = jax.random.PRNGKey(0)
        pv = {n: self._param_vals[n] for n in self._diff_names}
        av = {n: self._param_vals[n] for n in self._aux_names}
        return fn, (pv, av, self._opt_state, jnp.float32(1), key,
                    *datas, *labels)

    def lowered(self, data, label, key=None):
        """Lower (but do not run) the full sharded train step for this batch
        and return the jax ``Lowered`` object — `.compile().as_text()` gives
        the post-GSPMD HLO, the supported way to AUDIT collective placement
        (which all-reduces/all-gathers the partitioner inserted and where).
        Does not mutate trainer state."""
        fn, args = self._inspection_step(data, label, key)
        return fn.lower(*args)

    def audit_step(self, data, label, key=None):
        """Compile the full train step WITHOUT donation, run it on the
        current state WITHOUT mutating the trainer, and return
        ``(collective_counts, loss)`` — the collective-placement +
        semantics audit primitive used by dryrun_multichip and the
        parallelism tests."""
        from .collectives import collective_counts
        fn, args = self._inspection_step(data, label, key)
        compiled = fn.lower(*args).compile()
        counts = collective_counts(compiled.as_text())
        loss = float(jax.device_get(compiled(*args)[3]))
        return counts, loss

    # ------------------------------------------------------- checkpointing
    def device_snapshot(self):
        """Copy the full DEVICE-resident training state (params, aux,
        optimizer slots, step counter) — the resilience rollback ring's
        primitive. jnp.copy is mandatory: the jitted step donates its
        inputs, so uncopied references would be invalidated (deleted
        buffers) by the very next step. No host transfer happens; the
        copies stay sharded on device."""
        return {
            "step": self._step_count,
            "params": {n: jnp.copy(v) for n, v in self._param_vals.items()},
            "opt": {n: tuple(jnp.copy(s) for s in st)
                    for n, st in self._opt_state.items()},
        }

    def restore_device_snapshot(self, snap):
        """Rewind to a device_snapshot(). Copies again on the way in, so
        the ring entry survives the restored state being donated by later
        steps (one snapshot can be restored repeatedly)."""
        self._param_vals = {n: jnp.copy(v)
                            for n, v in snap["params"].items()}
        self._opt_state = {n: tuple(jnp.copy(s) for s in st)
                           for n, st in snap["opt"].items()}
        self._step_count = int(snap["step"])

    def state_dict(self):
        """Flat name -> array dict of the FULL training state (params,
        aux, optimizer slots, step counter) for utils.CheckpointManager.
        Arrays may be device-sharded; the manager's host snapshot gathers
        them (a jax.Array materializes as one global ndarray)."""
        flat = {"param/" + n: v for n, v in self._param_vals.items()}
        for n, st in self._opt_state.items():
            for i, s in enumerate(st):
                flat["opt%d/%s" % (i, n)] = s
        flat["step"] = jnp.int32(self._step_count)
        return flat

    def load_state_dict(self, flat):
        """Restore state_dict() output (arrays or NDArrays, e.g. from
        CheckpointManager.restore). Every array is device_put back under
        its proper sharding — params replicated/tp-ruled, optimizer slots
        ZeRO-sharded when the trainer is zero1."""
        def raw(v):
            return v._data if hasattr(v, "_data") else v
        for n in self._diff_names + self._aux_names:
            key = "param/" + n
            if key not in flat:
                raise KeyError("checkpoint missing %s" % key)
            v = raw(flat[key])
            # restored params follow the trainer's CONFIGURED storage
            # precision (a bf16-param trainer stays bf16 even from an
            # fp32 checkpoint — no silent retrace); when no param_dtype
            # is configured the host array goes straight to device_put
            # (single transfer)
            host_dtype = getattr(v, "dtype", None)  # host-side, no transfer
            if self._param_dtype is not None and n in self._diff_names \
                    and host_dtype is not None \
                    and jnp.issubdtype(host_dtype, jnp.floating):
                v = jnp.asarray(v, dtype=self._param_dtype)
            self._param_vals[n] = _gput(v, self._param_shardings[n])
        new_opt = {}
        for n, st in self._opt_state.items():
            sh = self._zero_shardings.get(n, self._param_shardings[n]) \
                if self._zero1_mode else self._param_shardings[n]
            slots = []
            for i in range(len(st)):
                key = "opt%d/%s" % (i, n)
                if key not in flat:
                    raise KeyError("checkpoint missing %s" % key)
                v = jnp.asarray(raw(flat[key]))
                # restored slots follow the trainer's CONFIGURED state
                # precision (a bf16-state trainer stays bf16 even from an
                # fp32 checkpoint, and vice versa — no silent retrace)
                if v.dtype != st[i].dtype:
                    v = v.astype(st[i].dtype)
                slots.append(_gput(v, sh))
            new_opt[n] = tuple(slots)
        self._opt_state = new_opt
        self._step_count = int(jax.device_get(raw(flat["step"])))

    def sync_to_block(self):
        """Copy sharded params back into the gluon block's NDArrays."""
        for n in self._diff_names + self._aux_names:
            self._params_ref[n]._data._data = jax.device_put(
                self._param_vals[n])

    @property
    def param_values(self):
        return dict(self._param_vals)
