"""The largest expert's rows over the mean rows an expert, in one expert
layer of one decode step (the program's counters ``moe.max_expert_rows`` and
``moe.rows``, means over the traced window): what a seed's weights do to the balance.
1.0 is an even spread; the decode kernel's time follows the experts
touched, not this, but a seed whose routing collapses shows here first."""
from benchmarks.layer_metrics import _mla_moe


def read(red, run):
    means = _mla_moe.decode_means(run["config"], red)
    if means is None or not means[0]:
        return None
    return means[2] / (means[0] / run["config"]["n_routed_experts"])
