"""The decode step's share of its HBM roofline: for each of the traced
stretch's `engine/step` spans, the bytes that step needs (the family's
`step_bytes` at the span's `live` slots and `step` position) over the
chip's HBM bandwidth, averaged, over the mean device time of a
`jit_decode_step` call in the same stretch.  None where the program's
steps carry no `live` (a program without that argument) or the family
counts no bytes."""
from bench.lib import program_spans
from bench.lib.readers import PCT

DECODE = r"^jit_decode_step\b"


def read(run):
    step_bytes = getattr(run.cell.family, "step_bytes", None)
    lg = program_spans.log(run)
    if step_bytes is None or lg is None or run.peaks is None:
        return None
    steps = [s for s in lg.named("engine/step") if "live" in s.end_args]
    total, count = run.trace.module_time(DECODE)
    if not steps or not count or total <= 0:
        return None
    need_s = sum(step_bytes(run.spec, s.end_args["live"], s.args["step"])
                 for s in steps) / run.peaks["hbm_bytes_per_s"]
    return need_s / len(steps) / (total / count) * PCT
