"""Device time per call of the engine's decode step (``ServeEngine``'s
jitted ``_decode_step``: the KV page resolve, the scanned layers with
paged attention, the unembedding), over its calls inside the traced
ticks, in ms.  The calls are found by joining the trace's ops with the
step's compiled HLO (:func:`chipbench.ticks.decode_calls`)."""

from chipbench import ticks as ticks_lib

NAME = "decode_ms.serve"


def read(run, trace, *, cell, peaks):
    spans = ticks_lib.spans(run, trace)
    if spans is None:
        return None
    calls = ticks_lib.decode_calls(trace, cell, spans)
    if not calls:
        return None
    return 1e3 * sum(c.seconds for c in calls) / len(calls)
