"""Host time splitting a batch's results into its instances per
instance, ms (host clock): the program's ``fabric:split`` spans of the
traced calls (``FabricBatchResult.results()`` / ``instance()``),
summed, over the instances they split (their ``instances`` stats
summed)."""

from bench import program_trace as pt


def read(ctx):
    spans = pt.spans("split")
    n = sum(int(st.get("instances", 0)) for _, _, st in spans)
    if not spans or not n:
        return None
    return 1e-6 * sum(d for _, d, _ in spans) / n
