"""The decode program's share of its roofline: the bytes and FLOPs a
DeepSeek-V2 decode step needs (weights once, a held expert's only if a
token of the step chose it, the cached latent rows each sequence attends
to, one new row per sequence and layer; ``bench.models.deepseek_v2``) at
the least time the chip's peaks allow, over the program's measured device
time per call.  Averaged over the decode steps of the traced stretch."""
from bench.harness import counts
from bench.metrics._serve import per_call
from bench.models import deepseek_v2 as fam


def read(run):
    pc = per_call(run, "serve_decode")
    steps = [s for s in run.record.get("traced_steps", []) if s[3]]
    if pc is None or not steps:
        return None
    m = run.cell.model
    nbytes = sum(fam.decode_bytes(m, s[3]) for s in steps) / len(steps)
    flops = sum(fam.decode_flops(m, s[3]) for s in steps) / len(steps)
    return counts.roofline_share(flops, nbytes, pc[0], run.peaks)
