"""Host time marshalling the engine's operands per simulation, ms (host
clock): the program's ``fabric:marshal`` spans of the traced calls
(``CompiledFabric._execute`` / ``fabric._execute_batch``: padding, host
arrays handed to the device, stacking a batch), summed, over the
simulations they carried (their ``instances`` stats summed)."""

from bench import program_trace as pt


def read(ctx):
    spans = pt.spans("marshal")
    sims = sum(int(st.get("instances", 0)) for _, _, st in spans)
    if not spans or not sims:
        return None
    return 1e-6 * sum(d for _, d, _ in spans) / sims
