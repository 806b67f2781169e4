"""Block / HybridBlock — the Gluon model API.

Reference parity: python/mxnet/gluon/block.py (Block:127, HybridBlock:671,
hybridize -> _build_cache -> CachedOp :748-795, SymbolBlock:952) per SURVEY
§2.6 and call stack §3.3.

TPU-first redesign of CachedOp: ``hybridize()`` turns the block's forward
into ONE jit-compiled XLA program (per input-signature, like the reference's
shape-specialized graph cache). Under autograd the compiled program is
recorded on the tape as a single node — exactly the reference's ``_CachedOp``
single-tape-node semantic — so ``loss.backward()`` runs the compiled
backward (jax.vjp of the whole program, XLA-compiled too). BatchNorm moving
stats and dropout RNG are explicit side-channels of the traced function
(XLA needs pure functions; the reference instead mutates aux arrays).
"""

import re
import threading

import jax
import jax.numpy as jnp

from ..ndarray import NDArray
from .. import ndarray as nd
from .. import ops as _ops
from .. import autograd as _ag
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "current_trace"]


# ---------------------------------------------------------------------------
# naming (reference: _BlockScope)
# ---------------------------------------------------------------------------

class _BlockScope:
    _current = threading.local()
    _counters = {}

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                count = _BlockScope._counters.get(hint, 0)
                prefix = "%s%d_" % (hint, count)
                _BlockScope._counters[hint] = count + 1
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *args):
        if self._block._empty_prefix:
            return False
        _BlockScope._current.value = self._old_scope
        return False


# ---------------------------------------------------------------------------
# trace context (the XLA-tracing analogue of CachedOp graph capture)
# ---------------------------------------------------------------------------

class _TraceCtx:
    def __init__(self, param_map, key, training, mesh_ctx=None):
        self.param_map = param_map    # full param name -> jax tracer
        self.aux_updates = {}         # full param name -> jax tracer (new value)
        self.key = key
        self.training = training
        self.F = _ops                 # op namespace (symbol module for export)
        # the ShardedTrainer's Mesh (when tracing under one): blocks that
        # own a parallelism axis (PipelineStack -> pp, MoEBlock -> ep)
        # read it to engage their sharded execution path
        self.mesh_ctx = mesh_ctx

    def take_key(self):
        if self.key is None:  # symbolic export trace: no RNG
            return None
        self.key, sub = jax.random.split(self.key)
        return sub


_trace_state = threading.local()


def current_trace():
    return getattr(_trace_state, "ctx", None)


def trace_on_one_device():
    """False inside a ShardedTrainer step traced over a mesh of several
    devices. jit refuses to partition a Mosaic kernel over a mesh ("wrap
    the call in a shard_map"), whatever its operands' shardings, so the
    Pallas fast paths are taken only in programs for one device and a
    step over a mesh takes the XLA forms."""
    ctx = current_trace()
    mesh = getattr(ctx, "mesh_ctx", None) if ctx is not None else None
    return mesh is None or mesh.size == 1


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

class Block:
    """Base class for all layers/models (dynamic graph, eager ops)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr) \
            if self._children else self.__class__.__name__ + "()"

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret._params.update(
                {name: value for name, value in self.params.items()
                 if pattern.match(name)})
        for child in self._children.values():
            sub = child.collect_params(select)
            if not select:
                ret.update(sub)
            else:
                ret._params.update(sub._params)
        return ret

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)
        for _, param in self._reg_params.items():
            param.cast(dtype)

    def zero_grad(self):
        self.collect_params().zero_grad()

    # -- checkpoint ----------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from ..ndarray import save as nd_save
        nd_save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from ..ndarray import load as nd_load
        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise IOError("Parameter %s is missing in file %s" % (name, filename))
        for name, value in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise IOError("Parameter %s in file %s is not present in this Block"
                                  % (name, filename))
                continue
            params[name].set_data(value)

    # older API names kept for reference-script compatibility
    save_params = save_parameters

    def load_params(self, filename, ctx=None, **kwargs):
        self.load_parameters(filename, ctx=ctx, **kwargs)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- forward -------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary (reference: block.py summary)."""
        rows = []

        def walk(block, path):
            n_params = sum(int(jnp.prod(jnp.asarray(p.shape)))
                           for p in block._reg_params.values()
                           if p.shape is not None)
            rows.append((path or block.name, type(block).__name__, n_params))
            for cname, child in block._children.items():
                walk(child, (path + "." if path else "") + cname)

        walk(self, "")
        out = self(*inputs)
        total = sum(r[2] for r in rows)
        lines = ["%-40s %-20s %12s" % ("Layer", "Type", "Params"), "-" * 74]
        lines += ["%-40s %-20s %12d" % r for r in rows]
        lines += ["-" * 74, "Total params: %d" % total]
        print("\n".join(lines))
        return out


def _indent(s, num_spaces):
    lines = s.split("\n")
    first = lines.pop(0)
    return first + ("\n" + "\n".join(" " * num_spaces + line for line in lines)
                    if lines else "")


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------

class HybridBlock(Block):
    """A Block that can be compiled to one XLA program via ``hybridize()``."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._jit_cache = {}
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._jit_cache = {}
        super().hybridize(active, **kwargs)

    def infer_shape(self, *args):
        """Finish deferred parameter shapes from example inputs. Layers
        override ``_shape_hook``; containers recurse through forward."""
        self._ensure_init(*args)

    def _shape_hook(self, *args):
        """Per-layer deferred-shape rule; default: nothing to infer."""

    def _ensure_init(self, *args):
        """Make sure every parameter of the subtree is materialized, running
        one eager (non-hybrid) forward if deferred shapes remain."""
        pending = [p for p in self.collect_params().values()
                   if p._data is None and p._deferred_init is not None]
        if not pending:
            return
        with _DisableHybrid(self):
            with _ag.pause():
                self.forward(*args)
        still = [p for p in self.collect_params().values()
                 if p._data is None and p._deferred_init is not None]
        if still:
            raise DeferredInitializationError(
                "Could not infer shapes for %s" % [p.name for p in still])

    # -- the compiled path ---------------------------------------------------
    def _call_compiled(self, *args):
        arg_arrays = [a for a in args if isinstance(a, NDArray)]
        self._ensure_init(*args)

        params = {p.name: p for p in self.collect_params().values()}
        diff_names = sorted(n for n, p in params.items()
                            if p.grad_req != "null" and p._data is not None)
        aux_names = sorted(n for n, p in params.items()
                           if p.grad_req == "null" and p._data is not None)
        training = _ag.is_training()
        try:
            static_sig = tuple(a if not isinstance(a, NDArray) else None
                               for a in args)
            hash(static_sig)
        except TypeError:
            static_sig = ()
        cache_key = (training, len(diff_names), len(aux_names), static_sig)
        jitted = self._jit_cache.get(cache_key)
        if jitted is None:
            jitted = self._build_jit(diff_names, aux_names, training, args)
            self._jit_cache[cache_key] = jitted
        out_tree = jitted[2]

        diff_vals = [params[n]._data._data for n in diff_names]
        aux_vals = [params[n]._data._data for n in aux_names]
        key = _ops.random.next_key()
        fwd_jit, bwd_jit, _ = jitted
        in_vals = [a._data for a in arg_arrays]
        raw_outs, aux_new = fwd_jit(in_vals, diff_vals, aux_vals, key)
        outs_and_aux = tuple(raw_outs) + tuple(aux_new)
        node = None

        if _ag.is_recording():
            # record the compiled program as ONE tape node (reference:
            # _CachedOp single node). Backward = jitted vjp with the forward
            # rematerialized inside (same RNG key => identical dropout masks).
            n_out = len(raw_outs)

            def vjp_fn(arg):
                cts = list(arg) if isinstance(arg, tuple) else [arg]
                cts_flat, cts_aux = cts[:n_out], cts[n_out:]
                g_ins, g_dvs = bwd_jit(in_vals, diff_vals, aux_vals, key,
                                       cts_flat, cts_aux)
                return tuple(g_ins) + tuple(g_dvs)

            node = _ag.TapeNode(
                arg_arrays + [params[n]._data for n in diff_names], vjp_fn,
                len(outs_and_aux), [(o.shape, o.dtype) for o in outs_and_aux],
                op_name="CachedOp(%s)" % self.name)

        n_out = len(outs_and_aux) - len(aux_names)
        outs = []
        for i in range(n_out):
            a = NDArray(outs_and_aux[i])
            if node is not None:
                a._node = node
                a._out_index = i
            outs.append(a)
        # apply aux updates (moving stats) outside the tape
        for j, nme in enumerate(aux_names):
            params[nme]._data._data = outs_and_aux[n_out + j]
        result = out_tree(outs)
        return result

    def _build_jit(self, diff_names, aux_names, training, example_args):
        block = self
        out_container = {}

        def pure_fn(input_vals, diff_vals, aux_vals, key):
            param_map = dict(zip(diff_names, diff_vals))
            param_map.update(zip(aux_names, aux_vals))
            ctx = _TraceCtx(param_map, key, training)
            prev = getattr(_trace_state, "ctx", None)
            _trace_state.ctx = ctx
            try:
                # rebuild args: substitute NDArray slots with tracers
                it = iter(input_vals)
                new_args = [next(it) if isinstance(a, NDArray) else a
                            for a in example_args]
                # forward() routes to hybrid_call while a trace ctx is active,
                # and lets blocks with custom traced forwards (RNN) hook in.
                out = block.forward(*new_args)
            finally:
                _trace_state.ctx = prev
            flat, rebuild = _flatten_outputs(out)
            out_container["rebuild"] = rebuild
            aux_new = [ctx.aux_updates.get(n, param_map[n]) for n in aux_names]
            return flat, aux_new

        fwd_jit = jax.jit(pure_fn)

        def bwd(input_vals, diff_vals, aux_vals, key, cts_flat, cts_aux):
            def f(ins, dvs):
                return pure_fn(ins, dvs, aux_vals, key)
            _, vjp = jax.vjp(f, input_vals, diff_vals)
            return vjp((list(cts_flat), list(cts_aux)))

        bwd_jit = jax.jit(bwd)
        # learn the output structure via an abstract trace only (no execution)
        params = {p.name: p for p in self.collect_params().values()}
        arg_arrays = [a._data for a in example_args if isinstance(a, NDArray)]
        jax.eval_shape(pure_fn, arg_arrays,
                       [params[n]._data._data for n in diff_names],
                       [params[n]._data._data for n in aux_names],
                       jax.random.PRNGKey(0))
        rebuild = out_container["rebuild"]
        return (fwd_jit, bwd_jit, rebuild)

    def hybrid_call(self, *args, **extra):
        """Forward used inside a trace: route to hybrid_forward with param
        tracers looked up from the active trace context. ``extra`` =
        caller keyword arguments (e.g. keyword-only model inputs), passed
        through alongside the param kwargs."""
        ctx = current_trace()
        kwargs = dict(extra)
        for local_name, p in self._reg_params.items():
            if p.name in ctx.param_map:
                kwargs[local_name] = ctx.param_map[p.name]
            elif p._data is not None:  # e.g. Constant not in maps
                kwargs[local_name] = p._data._data
        return self.hybrid_forward(ctx.F, *args, **kwargs)

    def forward(self, *args, **extra):
        if current_trace() is not None:
            return self.hybrid_call(*args, **extra)
        if self._active:
            if extra:
                raise TypeError(
                    "hybridized blocks take positional inputs only; got "
                    "keyword arguments %s" % sorted(extra))
            return self._call_compiled(*args)
        # eager path: params as NDArrays, F = mx.nd
        try:
            kwargs = {ln: p.data() for ln, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._shape_hook(*args)
            kwargs = {ln: p.data() for ln, p in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **{**extra, **kwargs})

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Save symbol-json + params (reference: HybridBlock.export)."""
        from ..symbol import block_to_json
        json_str = block_to_json(self)
        with open("%s-symbol.json" % path, "w") as f:
            f.write(json_str)
        # keys must match the symbol's argument/aux names (the reference
        # writes arg:/aux:<full param name>), or SymbolBlock.imports and
        # model.load_checkpoint cannot rebind them.
        from ..ndarray import save as nd_save
        out = {}
        for p in self.collect_params().values():
            if p._data is None:
                continue
            tag = "aux:" if getattr(p, "_aux", False) else "arg:"
            out[tag + p.name] = p.data()
        nd_save("%s-%04d.params" % (path, epoch), out)

    def optimize_for(self, x, *args, backend=None, **kwargs):
        self.hybridize(True)
        return self(x, *args)


class _DisableHybrid:
    def __init__(self, block):
        self.block = block
        self.saved = []

    def __enter__(self):
        def walk(b):
            if isinstance(b, HybridBlock):
                self.saved.append((b, b._active))
                b._active = False
            for c in b._children.values():
                walk(c)
        walk(self.block)

    def __exit__(self, *a):
        for b, act in self.saved:
            b._active = act


def _flatten_outputs(out):
    """Flatten nested (tuple/list of) arrays; return (flat, rebuild)."""
    if isinstance(out, (list, tuple)):
        spec = type(out)
        subs = [_flatten_outputs(o) for o in out]
        flat = [x for s in subs for x in s[0]]
        lens = [len(s[0]) for s in subs]
        rebuilds = [s[1] for s in subs]

        def rebuild(xs):
            res, i = [], 0
            for ln, rb in zip(lens, rebuilds):
                res.append(rb(xs[i:i + ln]))
                i += ln
            return spec(res) if spec is not tuple else tuple(res)
        return flat, rebuild
    return [out], (lambda xs: xs[0])


class SymbolBlock(HybridBlock):
    """Construct a block from a symbol graph (reference: SymbolBlock:952)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._sym_outputs = outputs
        self._sym_inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        from ..symbol import Symbol
        # arguments AND auxiliary states (running stats round-trip through
        # JSON as __aux__-marked vars; both need Parameter slots fed at
        # forward — reference SymbolBlock:975 aux_params handling)
        all_params = []
        if hasattr(outputs, "list_arguments"):
            all_params = list(outputs.list_arguments()) \
                + list(outputs.list_auxiliary_states())
        input_names = {s.name for s in self._sym_inputs}
        for name in all_params:
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load, var
        sym = sym_load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            from ..ndarray import load as nd_load
            loaded = nd_load(param_file)
            cleaned = {}
            for k, v in loaded.items():
                cleaned[k.split(":", 1)[1] if ":" in k else k] = v
            for name, p in ret.params.items():
                if name in cleaned:
                    p.set_data(cleaned[name])
        return ret

    def forward(self, *args):
        from ..symbol import executor_eval
        feed = {s.name: a for s, a in zip(self._sym_inputs, args)}
        for name, p in self.params.items():
            feed[name] = p.data()
        return executor_eval(self._sym_outputs, feed)

    def hybrid_forward(self, F, *args, **kwargs):
        raise RuntimeError("SymbolBlock routes through forward()")
