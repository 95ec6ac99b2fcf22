"""Tokens that share one held expert's weight read in a decode step: the
``expert_tokens`` count the program attaches to each ``serve.collect`` span
(choices routed to the experts held here, summed over the expert layers)
over expert layers x ``experts_held``, averaged over the decode steps of
the traced stretch.  None where the program attaches no such count."""
from bench.metrics._spans import spans


def read(run):
    got = [s for s in spans(run, ("serve.collect",)) or [] if "expert_tokens" in s.counts]
    if not got:
        return None
    m = run.cell.model
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    return sum(s.counts["expert_tokens"] / (layers * s.counts["experts_held"]) for s in got) / len(got)
