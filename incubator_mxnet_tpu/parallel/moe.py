"""Mixture-of-experts layers: a capacity layer over an `ep` axis, and a
dropless one.

Two layers with two contracts. ``moe_apply`` (below, first) is the
Switch/GShard capacity layer; ``moe_dropless`` (further down) routes every
token to its top-k experts whatever the load, sorts the routes by expert
and runs one grouped matrix product over the experts held: all of them, or
(``held``) the range of them that this chip holds of a layer shared by
expert parallelism, whose routes it picks out first and whose part of the
result it returns (no exchange: one chip's share, alone).

The capacity layer.

TPU-native formulation (Mesh-TensorFlow / Switch Transformer lineage):
token->expert routing is expressed as DENSE dispatch/combine einsums over
a fixed per-expert capacity — no dynamic shapes, everything rides the
MXU — and expert weights carry a leading E axis sharded over `ep`.
Constraining the dispatched activations to `P("ep", ...)` makes GSPMD
materialize the token redistribution as the all-to-all over ICI; the
combine einsum brings tokens home. Fully differentiable (router included,
via the straight-through gate weighting).
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..gluon.block import HybridBlock

__all__ = ["moe_apply", "moe_ffn", "MoEBlock", "moe_dropless"]


def moe_apply(x, gate_w, w1, b1, w2, b2, capacity_factor=1.25,
              ep_sharding=None, top_k=1, return_stats=False):
    """Top-k MoE feed-forward (k=1 = Switch semantics).

    x : (S, d) tokens (flatten batch x seq first)
    gate_w : (d, E) router
    w1, b1, w2, b2 : (E, d, h), (E, h), (E, h, d), (E, d) expert MLPs
    capacity_factor : per-expert capacity C = ceil(S*k/E * factor);
        tokens over capacity are DROPPED for that expert (output 0 from
        it — Switch/GShard semantics)
    ep_sharding : optional (mesh, axis) — constrains the dispatched
        (E, C, d) activations so the redistribution lowers to the ep
        collective.
    top_k : number of experts per token; each token's k routes get their
        own capacity slot, gates renormalized over the chosen k
        (GShard-style; k=1 reproduces the Switch formulation exactly).
    return_stats : also return a telemetry dict — dropped-ROUTE fraction
        (of the S*k token-expert routes; a top-2 token whose second route
        overflows still gets output from its first) and per-expert load —
        so over-capacity drops are OBSERVABLE, not silent (VERDICT r3
        weak #5).

    Returns (out (S, d), aux_loss[, stats]) — aux_loss is the Switch
    load-balance loss (mean over experts of fraction_tokens *
    fraction_router_prob * E).
    """
    S, d = x.shape
    E = gate_w.shape[1]
    k = int(top_k)
    assert 1 <= k <= E, "top_k must be in [1, %d]" % E
    C = max(1, int(-(-(S * k * capacity_factor) // E)))

    logits = x @ gate_w                                   # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                  # (S, k)
    if k == 1:
        # Switch: the RAW router probability scales the expert output
        # (renormalizing a single choice would collapse it to 1.0)
        gates = topv
    else:
        # GShard: the chosen k gates renormalize to mix to 1
        gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # routing bookkeeping stays fp32: a bf16 cumsum rounds queue
    # positions past 256 and double-books capacity slots.
    # queue positions are assigned route-major (all tokens' 1st choice,
    # then 2nd, ...) so lower-rank routes win capacity first.
    onehots32 = [jax.nn.one_hot(topi[:, j], E, dtype=jnp.float32)
                 for j in range(k)]                       # k x (S, E)
    stacked = jnp.concatenate(onehots32, axis=0)          # (k*S, E)
    pos_all = (jnp.cumsum(stacked, axis=0) - 1.0) * stacked

    dispatch = jnp.zeros((S, E, C), x.dtype)
    combine_w = jnp.zeros((S, E, C), x.dtype)
    n_dropped = jnp.zeros((), jnp.float32)
    for j in range(k):
        oh32 = onehots32[j]
        pos = pos_all[j * S:(j + 1) * S]                  # (S, E)
        in_cap = ((pos < C) * (oh32 > 0)).astype(x.dtype)
        pos_clamped = jnp.clip(pos.sum(-1).astype(jnp.int32), 0, C - 1)
        cap_oh = jax.nn.one_hot(pos_clamped, C, dtype=x.dtype)
        d_j = in_cap[:, :, None] * cap_oh[:, None, :]     # (S, E, C)
        dispatch = dispatch + d_j
        combine_w = combine_w + d_j * gates[:, j, None, None]
        n_dropped = n_dropped + jnp.sum(
            (oh32 > 0) & (pos >= C)).astype(jnp.float32)

    xin = jnp.einsum("sec,sd->ecd", dispatch, x)          # (E, C, d)
    if ep_sharding is not None:
        mesh, axis = ep_sharding
        xin = jax.lax.with_sharding_constraint(
            xin, NamedSharding(mesh, P(axis, None, None)))
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xin, w1) + b1[:, None, :])
    y = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]  # (E, C, d)
    if ep_sharding is not None:
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(axis, None, None)))
    out = jnp.einsum("sec,ecd->sd", combine_w, y)         # (S, d)

    # Switch load-balance auxiliary (encourages uniform expert usage);
    # computed over FIRST-choice assignments, the Switch/GShard recipe
    frac_tokens = onehots32[0].astype(x.dtype).mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = (frac_tokens * frac_probs).sum() * E
    if return_stats:
        load = dispatch.sum(axis=(0, 2))                  # tokens/expert
        stats = {"dropped_route_frac": n_dropped / float(S * k),
                 "expert_load": load,
                 "capacity": jnp.float32(C)}
        return out, aux, stats
    return out, aux


def moe_ffn(x, params, prefix, top_k=2, capacity_factor=1.25,
            ep_sharding=None):
    """Functional MoE feed-forward over MoEBlock-style flat param names.

    Pulls ``{prefix}gate_weight / expert_w1 / expert_b1 / expert_w2 /
    expert_b2`` out of a flat name->array dict and runs :func:`moe_apply`
    on (S, d) tokens, returning the mixed output only. This is the
    decode-path entry: the GPT decoder's paged forward is a pure
    function over its param dict (no gluon trace context), so it reuses
    the routing math without the HybridBlock wrapper.
    """
    out, _aux = moe_apply(
        x, params[prefix + "gate_weight"], params[prefix + "expert_w1"],
        params[prefix + "expert_b1"], params[prefix + "expert_w2"],
        params[prefix + "expert_b2"], capacity_factor,
        ep_sharding=ep_sharding, top_k=top_k)
    return out


# ------------------------------------------------------------- dropless
def _routes(logits, k, scoring, choice_bias, route_scale):
    """The router's decision from its float32 `logits` (N, E): -> (the k
    experts a token (N, k), their weights (N, k)). The choice is by score
    (+ `choice_bias`); the weights are the chosen experts' unbiased scores,
    renormalised, times `route_scale`."""
    if scoring == "softmax":
        probs, total_floor = jax.nn.softmax(logits, axis=-1), None
    elif scoring == "sigmoid":
        probs, total_floor = jax.nn.sigmoid(logits), 1e-20
    else:
        raise ValueError("no such scoring: %r" % (scoring,))
    if choice_bias is None:
        top_p, top_e = jax.lax.top_k(probs, k)              # (N, k)
    else:
        _, top_e = jax.lax.top_k(probs + choice_bias.astype(jnp.float32), k)
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    total = jnp.sum(top_p, axis=-1, keepdims=True)
    weights = top_p / (total if total_floor is None else total + total_floor)
    if route_scale != 1.0:
        weights = weights * route_scale
    return top_e, weights


def share_bound(tokens, top_k, experts, count):
    """The routes one pass of a layer that holds `count` of `experts`
    experts computes: twice the ``tokens top_k count / experts`` that even
    routing sends here, in whole 16-row tiles, two tiles at the least."""
    mean = -(-tokens * top_k * count // experts)
    return max(32, -(-2 * mean // 16) * 16)


def _pass_layout(sizes, slots, tile_rows):
    """Where a pass's routes (in their order, `sizes` (G,) of them a held
    expert) sit once every group is padded to whole tiles of `tile_rows`
    slots, read from the SLOT's side, so that the layout is filled by
    gathers alone: -> (the row of the order in each slot (slots,), whether
    the slot holds one (slots,) bool, the group of each tile (T,), the
    tiles used (1,), the slots of padding before each group's first route
    (G,), which turn a route's row into its slot). Tiles past the used
    ones repeat the last used group (``grouped_matmul_tiles`` fetches
    nothing for them); with tiles of one slot the layout is the order
    itself."""
    groups = sizes.shape[0]
    per_group = (sizes + tile_rows - 1) // tile_rows
    tile_end = jnp.cumsum(per_group)                # inclusive, by group
    used = tile_end[-1]
    tile = jnp.minimum(jnp.arange(slots // tile_rows, dtype=jnp.int32),
                       jnp.maximum(used - 1, 0))
    tile_group = jnp.minimum(jnp.sum(tile[:, None] >= tile_end[None, :],
                                     axis=1, dtype=jnp.int32), groups - 1)
    group = jnp.repeat(tile_group, tile_rows)      # slots, with no scatter
    slot = jnp.arange(slots, dtype=jnp.int32)
    rank = slot - (tile_end - per_group)[group] * tile_rows
    live = (rank < sizes[group]) & (slot // tile_rows < used)
    first_row = jnp.cumsum(sizes) - sizes
    return (first_row[group] + rank, live, tile_group, used.reshape(1),
            (tile_end - per_group) * tile_rows - first_row)


# TPU v5e peaks (benchmarks/peaks.py): bfloat16 FLOPs and HBM bytes a second
_MXU_FLOPS_PER_S, _HBM_BYTES_PER_S = 197e12, 819e9


def gathers_home(tokens, top_k, slots, width):
    """Whether a pass's (slots, width) float32 rows come home cheaper by
    each token's gather of its own `top_k` rows (4 tokens top_k width
    bytes at the HBM's peak) than by the (tokens, slots) float32 product
    at HIGHEST (six bfloat16 passes, 12 tokens slots width FLOPs at the
    MXU's peak): where the layout holds more than some 80 slots a route
    of a token. All four are static shapes."""
    return (4 * tokens * top_k * width / _HBM_BYTES_PER_S
            < 12 * tokens * slots * width / _MXU_FLOPS_PER_S)


def _held_routes(x, top_e, weights, experts, gate_w, up_w, down_w, held,
                 gated, use_kernel, interpret, up_out_in=False):
    """The routed part of a layer that holds the experts ``[first, first +
    count)`` alone -> (the held routes' weighted outputs summed a token
    (N, d) float32, stats).

    The N k routes are sorted on "which held expert, or none" (a stable
    sort; the routes of other chips' experts last), so the routes that
    fall here lead the order, grouped by expert, and their number is
    known. They are computed ``share_bound`` routes a pass, in as many
    passes as it takes (a ``fori_loop`` whose trip count is the held
    routes over the bound, rounded up: none where no route falls here,
    N k over the bound where every route does): dropless under any skew,
    with static shapes, and a pass moves its own rows and no others. A
    pass lays its routes out in the grouped product's tiles ONCE
    (``_pass_layout``; off the TPU the layout is the order itself and the
    product ``ragged_dot``), gathers the tokens' rows into that layout,
    runs the expert's products on it (three, two where `gate_w` is None)
    and brings the rows home with no scatter (a TPU walks a scatter's
    updates one by one: 0.9 ms of a 14 ms decode step at 128 rows x
    7,168, PERF.md section 6), in float32, those of other passes and
    chips under weight 0. Where a pass's layout is wide for its routes
    (``gathers_home``) a route's slot is its place in the order (the
    order's inverse, a second sort) less the pass's start plus its
    group's padding, and each token gathers its own k rows and sums them:
    one gather in, one gather and a weighted sum of k rows a token out.
    Where it is narrow the rows come home by ONE product with the (N,
    slots) matrix that holds a route's weight where the slot is the
    token's, at HIGHEST: its work grows with the layout where the
    gather's grows with the routes (224 GFLOP a prefill round of 2,048
    tokens over 20,352 slots, against 132 MB gathered: PERF.md section
    6)."""
    from ..ops.pallas.grouped_matmul import (grouped_matmul_available,
                                             grouped_matmul_tiles,
                                             tile_rows_for)
    n, k = top_e.shape
    first, count = held
    routes = n * k
    key = jnp.where((top_e >= first) & (top_e < first + count),
                    top_e - first, count).reshape(routes)
    bound = min(share_bound(n, k, experts, count), routes)
    order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                    (0, bound))                     # held routes first
    load = jnp.sum(key[:, None] == jnp.arange(count, dtype=key.dtype)[None],
                   axis=0, dtype=jnp.int32)
    end = jnp.cumsum(load)                  # a group's end in the order
    here = end[-1]
    if use_kernel is None:
        use_kernel = grouped_matmul_available()
    tiled = use_kernel or interpret
    tile_rows = tile_rows_for(bound, count) if tiled else 1
    slots = (bound + count * (tile_rows - 1)) // tile_rows * tile_rows
    gather = gathers_home(n, k, slots, x.shape[1])
    if gather:
        # each token's routes by rank, (k, N): gathered in this order, the
        # rows of one rank lie side by side as the sum over the ranks reads
        place = jnp.argsort(order[:routes]).astype(jnp.int32).reshape(n, k).T
        group = key.reshape(n, k).T
        held_here = group < count
        group = jnp.minimum(group, count - 1)
    else:
        flat_w = weights.reshape(routes)
        tokens = jnp.arange(n, dtype=jnp.int32)

    def one_pass(p, out):
        lo = p * bound
        sizes = (jnp.clip(end, lo, lo + bound)
                 - jnp.clip(end - load, lo, lo + bound))
        row, live, tile_group, used, shift = _pass_layout(sizes, slots,
                                                          tile_rows)
        # a slot's route; a tile's padding reads token 0 under weight 0
        route = jnp.where(live, order[lo + row], 0)
        token = route // k
        moved = x[token]

        def product(rows, w, out_in=False):
            if tiled:
                return grouped_matmul_tiles(rows, w, tile_group, used,
                                            interpret, out_in)
            return jax.lax.ragged_dot(
                rows, jnp.swapaxes(w, 1, 2) if out_in else w, sizes,
                preferred_element_type=jnp.float32)
        y = product(gated(None if gate_w is None
                          else product(moved, gate_w),
                          product(moved, up_w, up_out_in)),
                    down_w)
        if gather:
            at = place - lo                 # a route's row in this pass
            mine = held_here & (at >= 0) & (at < bound)
            rows = y[jnp.where(mine, at + shift[group], 0)]  # (k, N, d)
            return out + jnp.sum(
                rows * jnp.where(mine, weights.T, 0.0)[:, :, None], axis=0)
        home = jnp.where(live[None, :] & (token[None, :] == tokens[:, None]),
                         flat_w[route][None, :], 0.0)       # (N, slots)
        return out + jnp.dot(home, y, precision="highest")
    passes = (here + bound - 1) // bound
    out = jax.lax.fori_loop(0, passes, one_pass,
                            jnp.zeros(x.shape, jnp.float32))
    return out, {"expert_load": load, "routes_elsewhere": routes - here,
                 "rows_moved": passes * slots}


def moe_dropless(x, router_w, gate_w, up_w, down_w, top_k, use_kernel=None,
                 interpret=False, return_stats=False, scoring="softmax",
                 choice_bias=None, route_scale=1.0, shared=None, held=None,
                 up_out_in=False):
    """Dropless top-k mixture of gated-SiLU experts, no biases.

    x : (N, d) tokens
    router_w : (d, E); gate_w, up_w : (E, d, f); down_w : (E, f, d)

    ``p = softmax(x router_w)`` over ALL experts in float32, the top-k of
    it renormalised to sum to one (``norm_topk_prob``), and every one of a
    token's k routes is computed, whatever the load on its expert:

        out = sum_{e in top-k} w_e * (silu(x gate_w[e]) * (x up_w[e])) down_w[e]

    `scoring` ``"sigmoid"`` scores each expert by itself, ``p =
    sigmoid(x router_w)``. `choice_bias` (E,) is added to the scores for
    the CHOICE of the top-k alone; the weights are the unbiased scores of
    the chosen, renormalised, times `route_scale`. `shared`: the ``(gate_w
    (d, fs), up_w, down_w (fs, d))`` of a shared expert that every token
    visits: dense, so computed by the plain products, added once a token
    and not a route (``expert_load`` counts routed experts only).

    A `gate_w` of None (the routed or the shared expert's): experts that
    are NOT gated, two products, ``relu(x up_w[e])^2 down_w[e]``.
    `up_out_in`: the routed experts' `up_w` is held (E, f, d), out by in
    as a linear layer keeps it (a TPU keeps an (E, d, f) array with d
    minor where f is no multiple of 128, and its product would copy every
    expert into the other order each forward; Nemotron-H's f is 1,856).

    The N x k routes are sorted by expert (a stable sort, so a token's
    routes keep their order inside a group) and each of the three expert
    products is ONE grouped product over the sorted rows
    (``ops.pallas.grouped_matmul``; `use_kernel` / `interpret` are its).
    Its cost follows the routes and the experts hit: an expert that gets
    no route costs nothing, one that gets them all gets them all. There
    is no capacity and no other path: this layer never becomes
    ``moe_apply``. Products accumulate in float32; the gated activation is
    rounded to x's dtype before the down product, the result after the
    weighted sum.

    `held` ``(first, count)``: this chip holds the experts ``[first,
    first + count)`` of a layer that several chips share by expert
    parallelism: ``gate_w / up_w / down_w`` are those `count` experts',
    ``router_w`` and ``choice_bias`` keep all E. The router decides over
    all E as published (the top-k, and the weights renormalised over ALL k
    chosen, held here or not), and the layer returns this chip's PART::

        out_here = sum_{e in top-k, first <= e < first + count} w_e Expert_e(x)
                   + Expert_shared(x)

    the routes that fall on the held experts (``_held_routes``: picked out
    of the N k first, computed ``share_bound`` routes a pass, none dropped
    under any skew, each pass's rows brought home by every token's gather
    of its own k rows, or by one (N, slots) product where the pass's
    layout is narrow for its routes: ``gathers_home``, from the static
    shapes) and the shared expert, which every chip computes
    alike. What the absent experts would add is another chip's to compute
    and an exchange's to sum: neither is here, and ``out_here`` is what
    the caller carries on. Summed over the shares of a layer, the shared
    part counted once, it is the uncut layer. ``held=None`` is the layer
    that holds every expert, by the body it always had.

    -> out (N, d), and with `return_stats` also ``{"expert_load": (E,)
    int32 routes an expert}``; under `held` ``expert_load`` is (count,),
    the routes each HELD expert got, beside ``routes_elsewhere`` (the
    routes on other chips' experts: N k less the held ones) and
    ``rows_moved`` (the rows the passes gathered into their first product:
    passes x the layout's slots, a compiled shape's number).
    """
    from ..ops.pallas.grouped_matmul import grouped_matmul
    n, d = x.shape
    experts = router_w.shape[1]
    k = int(top_k)
    if not 1 <= k <= experts:
        raise ValueError("top_k must be in [1, %d], got %d" % (experts, k))
    if held is not None:
        first, count = held
        if not (0 <= first and 1 <= count and first + count <= experts
                and up_w.shape[0] == count):
            raise ValueError(
                "held=(%d, %d) must lie inside the router's %d experts and "
                "come with %d experts' weights (got %d)"
                % (first, count, experts, count, up_w.shape[0]))

    def grouped(rows, w, sizes, out_in=False):
        return grouped_matmul(rows, w, sizes, use_kernel=use_kernel,
                              interpret=interpret, out_in=out_in)

    def gated(gate, up):
        if gate is None:
            return jnp.square(jax.nn.relu(up)).astype(x.dtype)
        return (jax.nn.silu(gate) * up).astype(x.dtype)
    top_e, weights = _routes(
        jnp.dot(x, router_w, preferred_element_type=jnp.float32), k, scoring,
        choice_bias, route_scale)
    if held is not None:
        out, stats = _held_routes(x, top_e, weights, experts, gate_w, up_w,
                                  down_w, held, gated, use_kernel, interpret,
                                  up_out_in)
    else:
        expert_of_route = top_e.reshape(n * k)
        order = jnp.argsort(expert_of_route, stable=True)   # sorted -> route
        load = jnp.zeros((experts,), jnp.int32).at[expert_of_route].add(1)
        stats = {"expert_load": load}
        rows = x[order // k]                                # (N k, d)
        hidden = gated(None if gate_w is None else grouped(rows, gate_w, load),
                       grouped(rows, up_w, load, up_out_in))
        y = grouped(hidden, down_w, load)                   # (N k, d) f32
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))             # route -> sorted
        out = jnp.sum(y[back].reshape(n, k, d) * weights[:, :, None], axis=1)
    if shared is not None:
        s_gate, s_up, s_down = shared
        out = out + jnp.dot(
            gated(None if s_gate is None
                  else jnp.dot(x, s_gate, preferred_element_type=jnp.float32),
                  jnp.dot(x, s_up, preferred_element_type=jnp.float32)),
            s_down, preferred_element_type=jnp.float32)
    out = out.astype(x.dtype)
    if return_stats:
        return out, stats
    return out


class MoEBlock(HybridBlock):
    """gluon layer: switch-MoE feed-forward over the last axis.

    Holds E expert MLPs as stacked parameters so `ShardedTrainer` rules
    like ``(r"moe.*_expert", P("ep", None, None))`` shard them over the
    expert axis. ``__call__`` returns the mixed output only; use
    ``forward_with_aux(x)`` to also get the Switch load-balance aux loss
    for the training objective (works on the eager tape and inside
    traces)."""

    def __init__(self, units, hidden, num_experts, capacity_factor=1.25,
                 top_k=1, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._hidden = hidden
        self._E = num_experts
        self._cf = capacity_factor
        self._top_k = int(top_k)
        from ..gluon.nn.basic_layers import _init_of
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(units, num_experts))
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(num_experts, units, hidden))
            self.expert_b1 = self.params.get(
                "expert_b1", shape=(num_experts, hidden),
                init=_init_of("zeros"))
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden, units))
            self.expert_b2 = self.params.get(
                "expert_b2", shape=(num_experts, units),
                init=_init_of("zeros"))

    def _ep_sharding(self):
        """(mesh, 'ep') when tracing under a ShardedTrainer whose mesh has
        an ep axis — constrains the dispatched activations so GSPMD lowers
        the token redistribution to the ep all-to-all (the trainer-side
        composition, VERDICT r3 #5)."""
        from ..gluon.block import current_trace
        ctx = current_trace()
        mesh = getattr(ctx, "mesh_ctx", None) if ctx is not None else None
        if mesh is not None and "ep" in mesh.axis_names \
                and dict(mesh.shape)["ep"] > 1:
            return (mesh, "ep")
        return None

    def _apply(self, x, gate_weight, expert_w1, expert_b1, expert_w2,
               expert_b2, with_aux):
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        if hasattr(flat, "_data"):          # eager NDArray path (tape)
            from ..ndarray.ndarray import _invoke_simple
            args = [flat, gate_weight, expert_w1, expert_b1, expert_w2,
                    expert_b2]

            def fn(xf, gw, w1, b1, w2, b2):
                out, aux = moe_apply(xf, gw, w1, b1, w2, b2, self._cf,
                                     top_k=self._top_k)
                return (out, aux) if with_aux else out
            res = _invoke_simple(fn, *args, op_name="MoEBlock")
            if with_aux:
                out, aux = res
                return out.reshape(shape), aux
            return res.reshape(shape)
        out, aux = moe_apply(flat, gate_weight, expert_w1, expert_b1,
                             expert_w2, expert_b2, self._cf,
                             ep_sharding=self._ep_sharding(),
                             top_k=self._top_k)
        out = out.reshape(shape)
        return (out, aux) if with_aux else out

    def hybrid_forward(self, F, x, gate_weight=None, expert_w1=None,
                       expert_b1=None, expert_w2=None, expert_b2=None):
        return self._apply(x, gate_weight, expert_w1, expert_b1, expert_w2,
                           expert_b2, with_aux=False)

    def forward_with_aux(self, x):
        """(mixed output, load-balance aux loss). Eager: both ride the
        autograd tape as NDArrays. Traced: raw arrays/tracers."""
        from ..gluon.block import current_trace
        if current_trace() is not None:
            ctx = current_trace()
            kw = {ln: ctx.param_map[p.name] for ln, p in
                  self._reg_params.items() if p.name in ctx.param_map}
            return self._apply(x, kw["gate_weight"], kw["expert_w1"],
                               kw["expert_b1"], kw["expert_w2"],
                               kw["expert_b2"], with_aux=True)
        kw = {ln: p.data() for ln, p in self._reg_params.items()}
        return self._apply(x, kw["gate_weight"], kw["expert_w1"],
                           kw["expert_b1"], kw["expert_w2"],
                           kw["expert_b2"], with_aux=True)
