"""Model FLOPs of everything served in the window (every prompt prefilled
and every token decoded, nothing recomputed; ``bench.models.deepseek_v2``)
over the window times the chip's peak."""
from bench.metrics._serve import peak_flops
from bench.models import deepseek_v2 as fam


def read(run):
    steps = run.record.get("steps")
    if not steps:
        return None
    m = run.cell.model
    flops = sum((fam.decode_flops(m, s[3]) if s[3] else 0)
                + sum(fam.prefill_flops(m, t) for t in s[4]) for s in steps)
    return 100.0 * flops / (run.seconds * peak_flops(run))
