"""The expert layer's grouped products against their roofline: the least
time for the weights of the experts that got a route (read once a product)
plus a row a route in and out, or for the routes' operations
(``benchmarks/costs_moe.py``), over the summed device time of the products'
events in the traced window.

Found by name: a Pallas launch carries its ``name=`` (``moe_grouped_matmul``,
``ops/pallas/grouped_matmul.py``); the launch XLA makes of
``jax.lax.ragged_dot`` is called ``ragged-dot-none`` (its small companion
``ragged-dot-metadata`` is not a product). The routes and the experts hit
are the traced call's own, from the engine's ``last_stats``: every forward
of the call (prefill chunks, denoising and store passes) runs three
products a layer."""

import re

from benchmarks import costs, costs_moe
from benchmarks.metrics_common import kernel_events

PRODUCT = re.compile(r"moe_grouped_matmul|ragged-dot(?!-metadata)")


def read(facts):
    moe = facts.get("traced_moe")
    events = kernel_events(facts, PRODUCT.search)
    if not moe or not events:
        return None
    cfg = facts["config"]
    least = costs.roofline_seconds(
        costs_moe.expert_product_flops(cfg, moe["routes"]),
        costs_moe.expert_product_bytes(cfg, moe["routes"],
                                       moe["experts_hit"]),
        facts["peaks"])
    return 100.0 * least / (sum(ev.dur_ns for ev in events) / 1e9)
