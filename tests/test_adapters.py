"""One paged-LM adapter under every model: the programs each adapter
builds, the ``last_stats`` entries it opens, what its heads leave for the
host, and a short greedy call's tokens and tallies, pinned at the CPU toy
configs of the benchmark's tests. The pinned values are those the tree
produced before the adapters shared one base class (``adapters.PagedLM``)
and the engine stopped naming models."""

import importlib
import os

import numpy as np
import pytest

from benchmarks import spec
from incubator_mxnet_tpu import generate
from incubator_mxnet_tpu.generate import GenerateEngine, adapters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = [[3, 5, 7, 2, 11, 1, 4, 9, 8, 6], [9, 8, 4, 4, 2], [7, 1, 6]]
NEW = 24

# the schemas ``GenerateEngine._admit`` opened by the adapter's attributes
MOE = {"forwards": 0, "routes": 0, "experts_hit": 0, "load_max_over_mean": [],
       "by_phase": {phase: dict.fromkeys(
           ("forwards", "routes", "experts_hit"), 0)
           for phase in ("prefill", "decode")}}
MLA = dict.fromkeys(
    ("absorbed_forwards", "expanded_forwards", "expanded_kernel_forwards",
     "expanded_rows", "absorbed_rows_live", "absorbed_rows_read"), 0)
LOOP = {phase: dict.fromkeys(
    ("forwards", "loop_steps", "layer_passes", "cache_rows_read",
     "cache_rows_written", "positions"), 0)
    for phase in ("prefill", "decode")}
EVA = {phase: dict.fromkeys(
    ("forwards", "windows_closed", "summary_rows_written",
     "window_rows_read", "summary_rows_read", "positions"), 0)
    for phase in ("prefill", "decode")}

ONE, BOTH = (False,), (False, True)
TOYS = {
    "gpt2_tiny": dict(
        adapter="GPTPagedLM", heads={"logits": ONE, "token": ONE}, opens={},
        token_reads=["token"], kv_reads=[]),
    "sdar_tiny": dict(
        adapter="SDARPagedLM",
        heads={"logits": ONE, "none": ONE, "denoise": ONE},
        opens={"moe": MOE}, token_reads=None, kv_reads=["expert_loads"]),
    "xing4_tiny": dict(
        adapter="MLAPagedLM", heads={"logits": ONE, "token": ONE, "none": ONE},
        opens={"moe": MOE, "mla": MLA},
        token_reads=["expert_loads", "token"], kv_reads=["expert_loads"]),
    "kimi_tiny": dict(
        adapter="MLAPagedLM", heads={"logits": ONE, "token": ONE, "none": ONE},
        opens={"moe": MOE, "mla": MLA},
        token_reads=["expert_loads", "token"], kv_reads=["expert_loads"]),
    "eva_tiny": dict(
        adapter="EvaPagedLM",
        heads={"logits": BOTH, "token": BOTH, "none": BOTH},
        opens={"eva": EVA}, token_reads=["token"], kv_reads=["window_rows"]),
    "ouro_tiny": dict(
        adapter="OuroPagedLM",
        heads={"logits": BOTH, "token": BOTH, "none": BOTH},
        opens={"loop": LOOP}, token_reads=["token"], kv_reads=["reached"]),
}

# ``load_max_over_mean``: (the forwards that appended one, their sum)
CALLS = {
    "gpt2_tiny": ([[945] * 17 + [288, 523, 62, 458, 458, 824, 683],
                   [988, 640, 1038, 1070, 499, 988, 45, 581, 434, 988, 1059,
                    988, 180, 1059, 485, 381, 1059, 381, 1059, 845, 464, 745,
                    945, 745],
                   [945] * 24], {}),
    "sdar_tiny": ([[138, 949, 160, 605, 605, 1074, 138, 1074, 268, 268, 431,
                    111, 131, 1021, 1021, 99, 988, 988, 285, 921, 111, 99,
                    140, 413],
                   [68, 821, 632, 494, 45, 214, 10, 339, 308, 339, 923, 250,
                    991, 821, 339, 1039, 991, 174, 587, 883, 174, 174, 725,
                    78],
                   [111, 473, 111, 1012, 1012, 388, 921, 929, 37, 376, 28,
                    177, 106, 339, 99, 99, 28, 290, 111, 1062, 99, 99, 59,
                    855]],
                  {"moe": {"forwards": 36, "routes": 1696, "experts_hit": 413,
                           "load_max_over_mean": (36, 101.833333333),
                           "by_phase": {
                               "prefill": {"forwards": 2, "routes": 64,
                                           "experts_hit": 17},
                               "decode": {"forwards": 34, "routes": 1632,
                                          "experts_hit": 396}}}}),
    "xing4_tiny": ([[45, 166, 83, 988, 358, 487, 696, 791, 124, 716, 1001,
                     155, 1094, 830, 804, 81, 788, 644, 491, 731, 75, 843,
                     872, 176],
                    [591, 83, 760, 591, 681, 591, 340, 1088, 505, 731, 10,
                     1031, 875, 878, 1004, 976, 593, 673, 682, 229, 1017, 229,
                     830, 682],
                    [56, 892, 127, 389, 733, 214, 449, 76, 682, 192, 1020,
                     1088, 437, 852, 123, 882, 328, 477, 830, 507, 123, 882,
                     791, 650]],
                   {"moe": {"forwards": 27, "routes": 432, "experts_hit": 244,
                            "load_max_over_mean": (27, 78.5),
                            "by_phase": {
                                "prefill": {"forwards": 3, "routes": 144,
                                            "experts_hit": 34},
                                "decode": {"forwards": 24, "routes": 288,
                                           "experts_hit": 210}}},
                    "mla": {"absorbed_forwards": 24, "expanded_forwards": 3,
                            "expanded_kernel_forwards": 0, "expanded_rows": 12,
                            "absorbed_rows_live": 1188,
                            "absorbed_rows_read": 4608}}),
    "kimi_tiny": ([[906, 681, 808, 533, 180, 389, 8, 808, 720, 1082, 104, 781,
                    1088, 912, 808, 590, 591, 439, 808, 287, 271, 37, 104,
                    781],
                   [562, 672, 507, 222, 197, 252, 8, 995, 198, 37, 175, 252,
                    1099, 941, 746, 431, 682, 252, 8, 123, 757, 699, 21,
                    1002],
                   [809, 682, 167, 252, 133, 513, 818, 597, 37, 1034, 104,
                    253, 151, 441, 967, 175, 286, 830, 0, 398, 46, 422, 398,
                    46]],
                  {"moe": {"forwards": 27, "routes": 242, "experts_hit": 122,
                           "routes_elsewhere": 1486, "rows_moved": 1344,
                           "load_max_over_mean": (27, 48.911363636),
                           "by_phase": {
                               "prefill": {"forwards": 3, "routes": 72,
                                           "experts_hit": 13,
                                           "routes_elsewhere": 504,
                                           "rows_moved": 192},
                               "decode": {"forwards": 24, "routes": 170,
                                          "experts_hit": 109,
                                          "routes_elsewhere": 982,
                                          "rows_moved": 1152}}},
                   "mla": {"absorbed_forwards": 24, "expanded_forwards": 3,
                           "expanded_kernel_forwards": 0, "expanded_rows": 12,
                           "absorbed_rows_live": 1188,
                           "absorbed_rows_read": 4608}}),
    "eva_tiny": ([[179, 52, 66, 282, 122, 216, 216, 80, 168, 49, 22, 204, 95,
                   216, 302, 113, 12, 286, 81, 50, 145, 226, 179, 302],
                  [31, 204, 195, 213, 213, 195, 213, 213, 213, 195, 195, 195,
                   153, 2, 26, 122, 102, 134, 5, 107, 161, 93, 61, 182],
                  [263, 30, 149, 87, 312, 81, 155, 305, 182, 42, 194, 128,
                   190, 157, 6, 30, 85, 157, 179, 102, 59, 233, 251, 223]],
                 {"eva": {"prefill": {"forwards": 3, "windows_closed": 0,
                                      "summary_rows_written": 0,
                                      "window_rows_read": 48,
                                      "summary_rows_read": 0,
                                      "positions": 48},
                          "decode": {"forwards": 24, "windows_closed": 1,
                                     "summary_rows_written": 8,
                                     "window_rows_read": 1260,
                                     "summary_rows_read": 8,
                                     "positions": 1260}}}),
    "ouro_tiny": ([[274, 274, 274, 244, 121, 87, 216, 274, 274, 244, 244, 244,
                    216, 274, 244, 216, 74, 244, 216, 216, 274, 216, 144,
                    287],
                   [12, 163, 12, 163, 163, 163, 12, 163, 12, 188, 12, 252, 12,
                    62, 252, 12, 12, 118, 39, 16, 12, 39, 252, 252],
                   [191, 172, 223, 140, 74, 143, 140, 276, 144, 270, 64, 270,
                    129, 270, 140, 270, 270, 270, 169, 140, 270, 270, 270,
                    270]],
                  {"loop": {"prefill": {"forwards": 3, "loop_steps": 9,
                                        "layer_passes": 27,
                                        "cache_rows_read": 108,
                                        "cache_rows_written": 135,
                                        "positions": 27},
                            "decode": {"forwards": 24, "loop_steps": 72,
                                       "layer_passes": 216,
                                       "cache_rows_read": 10692,
                                       "cache_rows_written": 648,
                                       "positions": 1260}}}),
}


@pytest.fixture(scope="module")
def built():
    """toy -> its adapter, built once a module: the programs compiled by
    one test serve the next."""
    made = {}

    def build(toy):
        if toy not in made:
            cfg = spec.load_json(os.path.join(
                ROOT, "tests", "benchmark", "configs", toy + ".json"))
            family = importlib.import_module(cfg["family"])
            cls = getattr(generate, TOYS[toy]["adapter"])
            kw = {} if cls is generate.GPTPagedLM else {"dtype": cfg["dtype"]}
            made[toy] = cls(family.reference.init_weights(cfg, 3),
                            family.program_config(cfg), **kw)
        return made[toy]
    return build


def _greedy(model, toy):
    engine = GenerateEngine(model, model.make_cache(3, max_len=64),
                            prefill_chunk=4, name="adapter_" + toy)
    return engine.generate(PROMPTS, max_new_tokens=NEW), engine.last_stats


@pytest.mark.parametrize("toy", list(TOYS))
def test_the_adapter_builds_the_programs_it_built_before(built, toy):
    model = built(toy)
    assert isinstance(model, adapters.PagedLM)
    assert set(model._fns) == {(head, fresh) for head, fresh_ones
                               in TOYS[toy]["heads"].items()
                               for fresh in fresh_ones}


@pytest.mark.parametrize("toy", list(TOYS))
def test_the_adapter_opens_the_tallies_the_engine_opened(built, toy):
    model = built(toy)
    opened = model.tallies()
    assert opened == TOYS[toy]["opens"]
    for entry in opened.values():       # each call's own, not the class's
        entry.clear()
    assert model.tallies() == TOYS[toy]["opens"]


@pytest.mark.parametrize("toy", list(TOYS))
def test_the_heads_leave_the_host_what_they_left_before(built, toy):
    """``forward_token`` and ``forward_kv`` fetch nothing and hand back the
    same read keys; a model without the token head declares none."""
    model = built(toy)
    cache = model.make_cache(1, max_len=64)
    slot = cache.alloc()
    reads = {}
    for head, width in (("forward_token", 1), ("forward_kv", 4)):
        if head == "forward_token" and "token" not in model.heads:
            continue
        read, *new = getattr(model, head)(np.zeros((1, width), np.int32),
                                          *cache.forward_inputs([slot]))
        written = next(iter(model.kv_groups.values()), model.kv_entries)
        assert len(new) == len(written)     # a chunk's group, stacked
        reads[head] = sorted(read)
    assert reads.get("forward_token") == TOYS[toy]["token_reads"]
    assert reads["forward_kv"] == TOYS[toy]["kv_reads"]


@pytest.mark.parametrize("toy", list(TOYS))
def test_a_greedy_call_serves_and_tallies_what_it_did_before(built, toy):
    tokens, stats = _greedy(built(toy), toy)
    want_tokens, want_stats = CALLS[toy]
    assert tokens == want_tokens
    got = {name: stats[name] for name in TOYS[toy]["opens"]}
    if "moe" in got:
        uneven = got["moe"].pop("load_max_over_mean")
        want_stats = dict(want_stats, moe=dict(want_stats["moe"]))
        count, total = want_stats["moe"].pop("load_max_over_mean")
        assert (len(uneven), sum(uneven)) == (count, pytest.approx(total))
    assert got == want_stats


@pytest.mark.parametrize("toy", list(TOYS))
def test_every_forward_takes_the_one_dispatch_point(built, toy,
                                                    monkeypatch):
    """A greedy call's every dispatch but a window's closing goes through
    ``_call``: the path a patched ``_call`` sees whole."""
    model = built(toy)
    seen = {"call": 0, "dispatch": 0}
    call, dispatch = type(model)._call, adapters._dispatch

    def counted_call(self, *args):
        seen["call"] += 1
        return call(self, *args)

    def counted_dispatch(*args, **kw):
        seen["dispatch"] += 1
        return dispatch(*args, **kw)
    monkeypatch.setattr(type(model), "_call", counted_call)
    monkeypatch.setattr(adapters, "_dispatch", counted_dispatch)
    _tokens, stats = _greedy(model, toy)
    closed = (stats["eva"]["decode"]["windows_closed"]
              + stats["eva"]["prefill"]["windows_closed"]
              if "eva" in stats else 0)
    assert seen["call"] > NEW
    assert seen["dispatch"] - seen["call"] == closed
