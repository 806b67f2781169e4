"""The model adapters: one paged-LM class under every decoder the engine
drives (the contract: ``engine``'s module docstring, docs/GENERATE.md).

``PagedLM`` owns what every adapter does: the weights cast to its dtype,
the cache it declares, one jitted program a ``(head, fresh)`` built from
the subclass's ``outputs``, the one dispatch point ``_call``, and
``forward`` / ``forward_token`` / ``forward_kv`` over it. A subclass
declares its config, heads, cache entries and host counts, and keeps its
own accounting of what its forwards did (``tallies`` / ``tally``).
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.paged_latent import (cache_row_width, latent_path,
                                       latent_block_size,
                                       paged_latent_decode_available,
                                       paged_latent_prefill_available,
                                       rows_walked)
from ..telemetry import catalog as _cat
from ..telemetry import tracing as _tr
from .paged_kv import PagedKVCache

__all__ = ["PagedLM", "GPTPagedLM", "SDARPagedLM", "MLAPagedLM",
           "EvaPagedLM", "OuroPagedLM", "NemotronHPagedLM"]


def _host_bytes(args):
    """Bytes of the host (numpy) arrays among `args`, lists looked into:
    what a jitted call given them has to ship to the device. An array
    that already lives on the device counts 0."""
    total = 0
    for a in args:
        if isinstance(a, (list, tuple)):
            total += _host_bytes(a)
        elif isinstance(a, np.ndarray):
            total += a.nbytes
    return total


def _dispatch(fn, params, args, programs=None, **attrs):
    """The call of an adapter's forward under its ``lm.dispatch`` span; a
    real span also counts the host bytes the call ships and carries
    `attrs`. `programs`: the adapter's (S, C) -> shipped executable of
    `fn`; one is preferred to the jitted `fn`, and retired when it
    refuses its arguments (compiled for other pools or weights, or an
    export of another signature)."""
    sp = _tr.span("lm.dispatch")
    if sp is not _tr.NULL_SPAN:     # counted only for a real span
        sp.set_attr("h2d_bytes", _host_bytes(args))
        for key, value in attrs.items():
            sp.set_attr(key, value)
    with sp:
        program = programs.get(args[0].shape) if programs else None
        if program is not None:
            try:
                return program(params, *args)
            except TypeError:
                del programs[args[0].shape]
        return fn(params, *args)


def _fetch(arrays):
    """`arrays` (what the caller reads of a forward's outputs: a list or
    a dict of them) as host arrays, under an ``lm.fetch`` span: the wait
    for the device, then the copies, all started before the first is
    waited for; the span counts the bytes copied."""
    with _tr.span("lm.fetch") as sp:
        out = jax.device_get(arrays)
        sp.set_attr("d2h_bytes", sum(
            a.nbytes for a in jax.tree_util.tree_leaves(out)))
    return out


def _by_phase(keys):
    """A tally kept apart for the prefill's forwards and the decode's."""
    return {phase: dict.fromkeys(keys, 0) for phase in ("prefill", "decode")}


class PagedLM:
    """A decoder over a ``PagedKVCache``, one jitted program a ``(head,
    fresh)`` and shape; the contract is ``engine``'s.

    A subclass sets ``kv_entries`` (entry name -> the (shape, dtype) of a
    position, in the order its forward takes the pools and returns the
    chunk's additions) and defines ``outputs``. It may declare
    ``state_entries`` (name -> the (shape, dtype) of a slot's state,
    after the kv entries: its forward then takes the rows' slots, ``fed``
    and the state pools, which its program is given donated, and returns
    the pools it wrote after the kv additions), ``kv_layers`` (entry ->
    the layers that hold it, where not every layer does), ``heads``,
    ``fresh_path`` (a chunk over empty slots has programs that read no
    pool), ``kv_groups``, ``block_row_bytes`` (the row a decode walk
    copies: blocks then hold ``latent_block_size`` of them), and define
    ``counts`` (a forward's host counts, kept in ``_said``),
    ``span_attrs`` (of its ``lm.dispatch``), ``prefill_attrs(forwards)``
    (of a prompt's ``gen.prefill``), ``host_logits`` (what ``forward``
    serves of the fetched logits), ``opens`` (the ``last_stats`` entries
    a call over it opens, ``tallies()``) and ``tally``.

    ``programs``: (S, C) -> a shipped executable of the ``"logits"``
    program, put there by an owner that ships them (the serving family);
    a call prefers it to the jit and retires it when it refuses its
    arguments."""

    heads = ("logits", "token", "none")
    fresh_path = False
    kv_groups = {}
    kv_layers = {}
    state_entries = {}
    block_row_bytes = None
    opens = {}

    def __init__(self, params, config, dtype):
        self.config = config
        self.dtype = None if dtype is None else jnp.dtype(dtype)
        self.params = {n: jnp.asarray(v, self.dtype)
                       for n, v in params.items()}
        self.num_layers = config["num_layers"]
        self.programs = {}
        self._fetched = self._said = {}
        self._fns = {(head, fresh): self._program(head, fresh)
                     for head in self.heads
                     for fresh in ((False, True) if self.fresh_path
                                   else (False,))}

    def _program(self, head, fresh):
        def pure(weights, tokens, *inputs):
            return self.outputs(head, fresh, weights, tokens, *inputs)
        states = len(self.state_entries)
        if not states:
            return jax.jit(pure)
        # the state pools, the forward's last arguments, are written in
        # place: (weights, tokens, *each group's lengths and tables, *kv
        # pools, slots, fed, *state pools)
        last = (2 + 2 * len(self.kv_groups or {None: 0})
                + len(self.kv_entries) + 2 + states)
        return jax.jit(pure, donate_argnums=tuple(
            range(last - states, last)))

    def outputs(self, head, fresh, weights, tokens, *inputs):
        """The traced forward of `head` -> ``(out, reads, new)``: the
        head's output (None for ``"none"``), a dict of the device arrays
        the host may read later, what the chunk adds to each cache entry
        (a tuple, stacked over layers)."""
        raise NotImplementedError

    # ------------------------------------------------------------ cache
    def cache_spec(self):
        return PagedKVCache.layer_spec(self.num_layers, self.kv_entries,
                                       groups=self.kv_groups,
                                       layers=self.kv_layers,
                                       states=self.state_entries)

    def make_cache(self, slots, max_len=None, **kw):
        """A cache of this adapter's entries; a `block_size` given wins
        over ``block_row_bytes``'s, then ``MXTPU_GEN_BLOCK_SIZE``."""
        max_len = max_len or self.config["max_len"]
        if self.block_row_bytes:
            kw.setdefault("block_size", latent_block_size(
                self.block_row_bytes, max_len))
        return PagedKVCache(slots, self.cache_spec(), max_len=max_len, **kw)

    # --------------------------------------------------------- forwards
    def weights(self):
        return self.params

    def lower(self, tokens, *inputs, head="logits", fresh=False):
        """A program lowered for arguments of these shapes (the weights
        may be ``jax.ShapeDtypeStruct``s)."""
        return self._fns[head, fresh].lower(self.weights(), tokens, *inputs)

    def _call(self, head, tokens, *inputs):
        """Every forward's one dispatch: the program of `head` (fresh where
        a chunk finds every slot empty) -> its outputs, on the device."""
        fresh = (self.fresh_path and tokens.shape[1] > 1
                 and not np.any(inputs[0]))
        self._fetched = {}
        out = _dispatch(self._fns[head, fresh], self.weights(),
                        (tokens,) + inputs,
                        self.programs if head == "logits" else None,
                        **self.span_attrs(tokens))
        self._said = self.counts(tokens, *inputs)
        return out

    def forward(self, tokens, *inputs):
        out, reads, new = self._call("logits", tokens, *inputs)
        logits, self._fetched = _fetch([out, reads])
        return (self.host_logits(logits),) + tuple(new)

    def forward_token(self, tokens, *inputs):
        token, reads, new = self._call("token", tokens, *inputs)
        return ({"token": token, **reads},) + tuple(new)

    def forward_kv(self, tokens, *inputs):
        head = "none" if "none" in self.heads else "logits"
        _out, reads, new = self._call(head, tokens, *inputs)
        return (reads,) + tuple(new)

    def host_logits(self, logits):
        return logits

    # ------------------------------------------------------- accounting
    def span_attrs(self, tokens):
        return {}

    def prefill_attrs(self, forwards):
        return {}

    def counts(self, tokens, *inputs):
        return {}

    def tallies(self):
        return copy.deepcopy(self.opens)

    def tally(self, stats, phase, name, read=None, committed=None):
        """Into a call's `stats` (what ``tallies`` opened) and the
        catalog's counters, labelled `name` and `phase`: with no keyword
        what the adapter just did (``_said``: a forward's counts, or its
        window closings'; what ``forward`` fetched); `read`, a forward's
        reads fetched a forward later; `committed`, the positions a commit
        stored."""


class _ExpertLM(PagedLM):
    """A decoder with expert layers: a forward's ``"expert_loads"``
    (expert layers, experts) go into ``last_stats["moe"]``; under
    ``experts_held`` they are the HELD experts' and two columns more, a
    layer's ``routes_elsewhere`` and ``rows_moved``."""

    opens = {"moe": {"forwards": 0, "routes": 0, "experts_hit": 0,
                     "load_max_over_mean": [],
                     "by_phase": _by_phase(("forwards", "routes",
                                            "experts_hit"))}}

    def tally(self, stats, phase, name, read=None, committed=None):
        if committed is not None:
            return
        loads = (self._fetched if read is None else read).get("expert_loads")
        if loads is None:
            return
        held, share = self.config.get("experts_held"), {}
        if held is not None:
            share = {"routes_elsewhere": int(loads[:, held[1]].sum()),
                     "rows_moved": int(loads[:, held[1] + 1].sum())}
            loads = loads[:, :held[1]]
        moe = stats["moe"]
        routes, hit = int(loads.sum()), int((loads > 0).sum())
        # the totals, and the same a second time under the forward's phase
        for kept in (moe, moe["by_phase"][phase]):
            kept["forwards"] += 1
            kept["routes"] += routes
            kept["experts_hit"] += hit
            for key, count in share.items():
                kept[key] = kept.get(key, 0) + count
        _cat.moe_routes.inc(routes, model=name, phase=phase)
        _cat.moe_experts_hit.inc(hit, model=name, phase=phase)
        for key, count in share.items():
            getattr(_cat, "moe_" + key).inc(count, model=name)
        # a share's layer may get no route at all: it has no fullest expert
        routed = loads[loads.sum(axis=1) > 0]
        if len(routed):
            uneven = float(np.mean(routed.max(axis=1) / routed.mean(axis=1)))
            moe["load_max_over_mean"].append(uneven)
            _cat.moe_load_max_over_mean.observe(uneven, model=name)


class GPTPagedLM(PagedLM):
    """``models/gpt.py``'s decoder, float32 as shipped. It has no ``"none"``
    head: ``forward_kv`` runs the ``"logits"`` program, which
    ``programs`` serves too. A program takes ``weights()``, a call's
    argument: replacing ``params`` swaps the weights under every one."""

    heads = ("logits", "token")

    def __init__(self, params, config):
        from ..models.gpt import gpt_config
        super().__init__(params, gpt_config(config), None)
        H = self.config["num_heads"]
        self.kv_entries = dict.fromkeys(
            "kv", ((H, self.config["units"] // H), jnp.float32))

    @property
    def params(self):
        """The weights by name, as a checkpoint holds them."""
        return self._params

    @params.setter
    def params(self, params):
        """Replacing the weights (a serving family's swap) derives their
        lookup table at once (``models.gpt.gpt_lookup_rows``: at GPT-2-XL a
        (25129, 3200) array, so that no forward copies the embedding into
        the order a gather reads); weights given as shapes (a compile for
        a described chip), its shape."""
        from ..models.gpt import gpt_lookup_rows
        # the old table is freed before the new one is made
        self._params, self._lookup_rows = params, None
        wte = params.get("wte")
        if isinstance(wte, jax.Array):
            self._lookup_rows = gpt_lookup_rows(wte)
        elif wte is not None:
            rows = jax.eval_shape(gpt_lookup_rows, wte)
            self._lookup_rows = jax.ShapeDtypeStruct(
                rows.shape, rows.dtype, sharding=wte.sharding)

    def weights(self):
        """``(params, lookup_rows)``."""
        return self._params, self._lookup_rows

    def outputs(self, head, fresh, weights, tokens, *inputs):
        from ..models import gpt
        params, lookup_rows = weights
        out, nk, nv = gpt.gpt_forward_paged(params, self.config, tokens,
                                            *inputs, lookup_rows, head=head)
        if head == "token":
            out = out[:, None]
        return out, {}, (jnp.stack(nk), jnp.stack(nv))


class SDARPagedLM(_ExpertLM):
    """``models/sdar_moe.py``'s block-diffusion decoder in `dtype`: it
    declares ``block_length`` and ``mask_id``, and has no token head.
    ``forward_denoise(..., masked=, steps_left=)``: each position's argmax
    ``x0`` and its probability, the schedule applied in the program
    (``steps_left`` an operand) -> ``(read, next_tokens, next_masked)``,
    nothing fetched, no K and V."""

    heads = ("logits", "none")

    def __init__(self, params, config, dtype="bfloat16"):
        from ..models.sdar_moe import sdar_config
        super().__init__(params, sdar_config(config), dtype)
        cfg = self.config
        self.kv_entries = dict.fromkeys(
            "kv", ((cfg["num_kv_heads"], cfg["head_dim"]), self.dtype))
        self.block_length = int(cfg["block_length"])
        self.mask_id = int(cfg["mask_id"])

        def denoise(params, tokens, masked, steps_left, lengths, tables,
                    kps, vps):
            from ..models import sdar_moe
            from .engine import GenerateEngine
            (x0, confidence), _nk, _nv, loads = sdar_moe.sdar_forward_paged(
                params, cfg, tokens, lengths, tables, kps, vps,
                head="choice")
            fixed = GenerateEngine._fix_most_confident(masked, confidence,
                                                       steps_left)
            return (jnp.where(fixed, x0, tokens), masked & ~fixed, x0,
                    confidence, loads)
        self._fns["denoise", False] = jax.jit(denoise)

    def outputs(self, head, fresh, params, tokens, *inputs):
        from ..models import sdar_moe
        out, nk, nv, loads = sdar_moe.sdar_forward_paged(
            params, self.config, tokens, *inputs, head=head)
        return out, {"expert_loads": loads}, (jnp.stack(nk), jnp.stack(nv))

    def forward_denoise(self, tokens, lengths, tables, k_pools, v_pools, *,
                        masked, steps_left):
        nxt, masked, x0, confidence, loads = self._call(
            "denoise", tokens, masked, np.int32(steps_left), lengths, tables,
            k_pools, v_pools)
        return ({"x0": x0, "confidence": confidence, "masked": masked,
                 "expert_loads": loads}, nxt, masked)


class MLAPagedLM(_ExpertLM):
    """``models/mla_moe.py``'s latent-attention decoders in `dtype`: ONE
    cache entry a layer, ``c``, a row of ``kv_rank + rope_dim`` values
    (576 in 640 lanes) shared by all heads. A forward's chunk width
    chooses its attention path, tallied into ``last_stats["mla"]``: the
    cached rows a chunk expanded again, or a decode step's walk read."""

    opens = dict(_ExpertLM.opens, mla=dict.fromkeys(
        ("absorbed_forwards", "expanded_forwards", "expanded_kernel_forwards",
         "expanded_rows", "absorbed_rows_live", "absorbed_rows_read"), 0))

    def __init__(self, params, config, dtype="bfloat16"):
        from ..models.mla_moe import mla_config
        super().__init__(params, mla_config(config), dtype)
        cfg = self.config
        width = cache_row_width(cfg["kv_rank"], cfg["rope_dim"])
        self.kv_entries = {"c": ((width,), self.dtype)}

    @property
    def block_row_bytes(self):
        (shape, dtype), = self.kv_entries.values()
        return shape[0] * dtype.itemsize

    def outputs(self, head, fresh, params, tokens, *inputs):
        from ..models import mla_moe
        out, rows, loads = mla_moe.mla_forward_paged(
            params, self.config, tokens, *inputs, head=head)
        if head == "token":
            out = out[:, None]
        return out, {"expert_loads": loads}, (jnp.stack(rows),)

    def span_attrs(self, tokens):
        return {"mla_path": latent_path(tokens.shape[1])}

    def counts(self, tokens, lengths, tables, pools):
        live, cfg = int(np.sum(lengths)), self.config
        if latent_path(tokens.shape[1]) == "expanded":
            return {"expanded_forwards": 1, "expanded_rows": live,
                    "expanded_kernel_forwards": int(
                        paged_latent_prefill_available(
                            pools[0], tokens.shape[1], cfg["kv_rank"],
                            cfg["nope_dim"], cfg["v_dim"]))}
        return {"absorbed_forwards": 1, "absorbed_rows_live": live,
                "absorbed_rows_read": rows_walked(
                    lengths, pools[0].shape[1], tables.shape[1],
                    paged_latent_decode_available(pools[0]))}

    def tally(self, stats, phase, name, read=None, committed=None):
        if read is None and committed is None:
            for key, count in self._said.items():
                stats["mla"][key] += count
                getattr(_cat, "mla_" + key).inc(count, model=name)
        super().tally(stats, phase, name, read, committed)


class EvaPagedLM(PagedLM):
    """``models/eva_byte.py``'s byte-level decoder in `dtype`: a window of
    exact keys and values (group ``window``: ``wk``, ``wv``) beside a
    summary a chunk of every closed window (``summary``: ``sk``, ``sv``),
    all heads side by side in a row. It declares ``window``; `interpret`
    runs the decode launches anywhere. ``forward`` serves head 0's logits,
    ``last_pred_logits`` (S, C, pred_heads, V) holds every head's;
    ``forward_kv`` reads ``"window_rows"`` (S,), the program's smallest
    output, so the host keeps one chunk ahead of the device."""

    fresh_path = True
    kv_groups = {"window": ("wk", "wv"), "summary": ("sk", "sv")}
    opens = {"eva": _by_phase(
        ("forwards", "windows_closed", "summary_rows_written",
         "window_rows_read", "summary_rows_read", "positions"))}

    def __init__(self, params, config, dtype="bfloat16", interpret=False):
        from ..models.eva_byte import eva_config
        super().__init__(params, eva_config(config), dtype)
        cfg = self.config
        self.interpret = interpret
        self.window = int(cfg["window"])
        self.summaries_per_window = self.window // int(cfg["chunk"])
        self.kv_entries = dict.fromkeys(
            ("wk", "wv", "sk", "sv"), ((cfg["units"],), self.dtype))
        self.last_pred_logits = None

        def close(params, wtables, wk, wv):
            from ..models import eva_byte
            sk, sv = eva_byte.eva_close_window(params, cfg, wtables, wk, wv)
            return jnp.stack(sk), jnp.stack(sv)
        self._close = jax.jit(close)

    def make_cache(self, slots, max_len=None, **kw):
        """The window group in blocks of 256 rows (or one window), the
        summary group a closing a block."""
        max_len = max_len or self.config["max_len"]
        per = self.summaries_per_window
        kw.setdefault("groups", {
            "window": {"max_len": self.window,
                       "block_size": min(self.window, 256)},
            "summary": {"max_len": max(1, max_len // self.window) * per,
                        "block_size": per}})
        return super().make_cache(slots, max_len=max_len, **kw)

    def outputs(self, head, fresh, params, tokens, wlen, *inputs):
        from ..models import eva_byte
        out, nk, nv = eva_byte.eva_forward_paged(
            params, self.config, tokens, wlen, *inputs, head=head,
            fresh=fresh, interpret=self.interpret)
        reads = {}
        if head == "token":
            out = out[:, None]
        elif head == "none":    # the stream; the window rows after
            reads = {"window_rows": wlen + tokens.shape[1]}
        return out, reads, (jnp.stack(nk), jnp.stack(nv))

    def host_logits(self, logits):
        V = self.config["vocab_size"]
        self.last_pred_logits = logits.reshape(logits.shape[:2] + (-1, V))
        return logits[..., :V]

    def lower_close(self, wtables, wk, wv):
        """The closing's program lowered for arguments of these shapes."""
        return self._close.lower(self.params, wtables, wk, wv)

    def close_windows(self, cache, slots, phase):
        """Each of `slots` whose window is full (host lengths): its rows
        pooled into summaries by one launch, committed to the summary
        group, the window restarted; a ``gen.window_close`` span a closing.
        -> what was closed (``last_stats["eva"]``'s names, kept in
        ``_said``), or None."""
        full = cache.group_lengths("window")
        due = [slot for slot in slots if full[slot] >= self.window]
        if not due:
            return None
        per = self.summaries_per_window
        for slot in due:        # one slot a launch: one compiled shape
            with _tr.span("gen.window_close", slots=1, rows_written=per,
                          phase=phase):
                sk, sv = _dispatch(
                    self._close, self.params,
                    (cache.tables_array([slot], "window"),)
                    + cache.group_pools("window"))
                cache.commit([slot], sk, sv, per, group="summary")
                cache.restart(slot, "window")
        self._said = {"windows_closed": len(due),
                      "summary_rows_written": per * len(due),
                      "window_rows_read": self.window * len(due)}
        return self._said

    def counts(self, tokens, wlen, wtables, slen, *rest):
        window_rows = int(wlen.sum()) + tokens.shape[1] * len(wlen)
        return {"forwards": 1, "window_rows_read": window_rows,
                "summary_rows_read": int(slen.sum()),
                "positions": int((slen // self.summaries_per_window).sum())
                * self.window + window_rows}

    def tally(self, stats, phase, name, read=None, committed=None):
        if read is None and committed is None:
            for key, count in self._said.items():
                stats["eva"][phase][key] += count
                getattr(_cat, "eva_" + key).inc(count, model=name,
                                                phase=phase)


class OuroPagedLM(PagedLM):
    """``models/ouro.py``'s looped decoder in `dtype`: ``num_layers``
    layers applied ``loop_steps`` = T times a token, a loop in the
    program; a ``k`` and a ``v`` entry a layer whose row holds every
    step's (T x H D lanes), in blocks sized by ONE step's row.
    `interpret` runs the decode launch anywhere; the exit gate's leaves
    are no argument of a program. ``forward_kv`` reads ``"reached"``
    (S,); ``last_stats["loop"]`` counts steps, passes and cache rows."""

    fresh_path = True
    opens = {"loop": _by_phase(
        ("forwards", "loop_steps", "layer_passes", "cache_rows_read",
         "cache_rows_written", "positions"))}

    def __init__(self, params, config, dtype="bfloat16", interpret=False):
        from ..models.ouro import ouro_config
        super().__init__({n: v for n, v in params.items()
                          if not n.startswith("exit_")},
                         ouro_config(config), dtype)
        cfg = self.config
        self.interpret = interpret
        self.loop_steps = int(cfg["loop_steps"])
        self.kv_entries = dict.fromkeys(
            "kv", ((self.loop_steps * cfg["units"],), self.dtype))
        self.block_row_bytes = cfg["units"] * self.dtype.itemsize

    def outputs(self, head, fresh, params, tokens, *inputs):
        from ..models import ouro
        out, nk, nv = ouro.ouro_forward_paged(
            params, self.config, tokens, *inputs, head=head, fresh=fresh,
            interpret=self.interpret)
        if head == "none":
            return None, {"reached": out}, (nk, nv)
        if head == "token":
            out = out[:, None]
        return out, {}, (nk, nv)

    def span_attrs(self, tokens):
        return {"loop_steps": self.loop_steps}

    def prefill_attrs(self, forwards):
        return {"loop_steps": self.loop_steps * forwards}

    def counts(self, tokens, lengths, *rest):
        steps, layers = self.loop_steps, self.num_layers
        live = int(np.sum(lengths))
        return {"forwards": 1, "loop_steps": steps,
                "layer_passes": steps * layers,
                "cache_rows_read": live * steps * layers, "positions": live}

    def tally(self, stats, phase, name, read=None, committed=None):
        loop = stats["loop"][phase]
        if committed is not None:   # rows written: one an entry
            loop["cache_rows_written"] += (committed * self.loop_steps
                                           * self.num_layers)
            loop["positions"] += committed
        elif read is None:
            for key, count in self._said.items():
                loop[key] += count
            _cat.gen_loop_steps.inc(self.loop_steps, model=name, phase=phase)


class NemotronHPagedLM(_ExpertLM):
    """``models/nemotron_h.py``'s hybrid state-space decoder in `dtype`: a
    ``k`` and a ``v`` entry on each attention layer alone (``kv_layers``),
    and two STATE entries on each Mamba layer, ``ssm`` (H, P, N) float32
    and ``conv`` (K - 1, Di + 2 G N) in `dtype`: device pools a slot a
    row, handed to the forward with the rows' slots and ``fed``, written
    in place and installed by the commit. `interpret` runs the decode
    step's state launch and the grouped products anywhere.
    ``last_stats["ssm"]`` counts by phase the forwards, the rows fed, the
    real positions, the scan's sub-chunks, the state's bytes and the
    attention layers' cached rows read."""

    opens = dict(_ExpertLM.opens, ssm=_by_phase(
        ("forwards", "rows", "positions", "scan_chunks", "state_bytes",
         "cache_rows_read")))

    def __init__(self, params, config, dtype="bfloat16", interpret=False):
        from ..models.nemotron_h import (layer_kinds, nemotron_config,
                                         state_shapes)
        cfg = nemotron_config(config)
        kinds = layer_kinds(cfg)
        self.mamba_layers, self.attention_layers = kinds["M"], kinds["*"]
        # declared before the programs are built: they donate the pools
        shapes = state_shapes(cfg)
        self.state_entries = {"ssm": (shapes["ssm"], jnp.dtype(jnp.float32)),
                              "conv": (shapes["conv"], jnp.dtype(dtype))}
        self.kv_entries = dict.fromkeys(
            "kv", ((cfg["num_kv_heads"], cfg["head_dim"]), jnp.dtype(dtype)))
        self.kv_layers = {"k": self.attention_layers,
                          "v": self.attention_layers,
                          "ssm": self.mamba_layers,
                          "conv": self.mamba_layers}
        super().__init__(params, cfg, dtype)
        self.interpret = interpret
        # a fed row's state, read once and written once a Mamba layer
        self.row_state_bytes = 2 * len(self.mamba_layers) * sum(
            int(np.prod(shape)) * dt.itemsize
            for shape, dt in self.state_entries.values())
        self._chunk = 1

    def outputs(self, head, fresh, params, tokens, *inputs):
        from ..models import nemotron_h
        out, loads, nk, nv, ssm, conv = nemotron_h.nemotron_forward_paged(
            params, self.config, tokens, *inputs, head=head,
            interpret=self.interpret)
        if head == "token":
            out = out[:, None]
        return (out, {"expert_loads": loads},
                (jnp.stack(nk), jnp.stack(nv), ssm, conv))

    def span_attrs(self, tokens):
        self._chunk = tokens.shape[1]
        return {"ssm_state_bytes": tokens.shape[0] * self.row_state_bytes}

    def prefill_attrs(self, forwards):
        return {"ssm_chunks": forwards * self._sub_chunks(self._chunk)}

    def _sub_chunks(self, chunk):
        """The scan's sub-chunks a row and Mamba layer of a forward
        `chunk` wide (a decode step runs none)."""
        return 0 if chunk == 1 else -(-chunk // self.config["ssm_chunk"])

    def counts(self, tokens, lengths, tables, k_pools, v_pools, slots, fed,
               *pools):
        rows = int(np.count_nonzero(fed))
        return {"forwards": 1, "rows": rows, "positions": int(np.sum(fed)),
                "scan_chunks": tokens.shape[0] * len(self.mamba_layers)
                * self._sub_chunks(tokens.shape[1]),
                "state_bytes": rows * self.row_state_bytes,
                "cache_rows_read": len(self.attention_layers)
                * int(np.sum(lengths))}

    def tally(self, stats, phase, name, read=None, committed=None):
        if read is None and committed is None:
            ssm = stats["ssm"][phase]
            for key, count in self._said.items():
                ssm[key] += count
            _cat.gen_ssm_state_bytes.inc(self._said["state_bytes"],
                                         model=name, phase=phase)
            if self._said["scan_chunks"]:
                _cat.gen_ssm_scan_chunks.inc(self._said["scan_chunks"],
                                             model=name)
        super().tally(stats, phase, name, read, committed)
