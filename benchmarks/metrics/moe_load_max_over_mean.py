"""The fullest expert's routes over the mean expert's: a forward's mean
over its layers (the engine's ``last_stats["moe"]``), median over the
forwards of the window's calls. 1 is even routing; the grouped product's
time follows the experts hit, the step's tail the fullest."""


def read(facts):
    return facts.get("moe_load_max_over_mean") or None
