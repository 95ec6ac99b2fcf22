"""The prefill program's share of its roofline: the FLOPs a DeepSeek-V2
prompt needs (every layer's matmuls per token, the held experts' expected
share, causal attention over keys expanded from the latent, the head for
the last position) and the bytes (the weights it reads, the prompt's latent
rows written; ``bench.models.deepseek_v2``) at the least time the chip's
peaks allow, over its device time per call (``prefill_prime``)."""
from bench.harness import counts
from bench.metrics._serve import per_call
from bench.models import deepseek_v2 as fam


def read(run):
    pc = per_call(run, "prefill_prime")
    lens = [t for s in run.record.get("traced_steps", []) for t in s[4]]
    if pc is None or not lens:
        return None
    m = run.cell.model
    flops = sum(fam.prefill_flops(m, t) for t in lens) / len(lens)
    nbytes = sum(fam.prefill_bytes(m, t) for t in lens) / len(lens)
    return counts.roofline_share(flops, nbytes, pc[0], run.peaks)
