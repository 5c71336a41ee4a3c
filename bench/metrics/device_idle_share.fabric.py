"""Share of the traced calls in which no operation ran on the device, %
(device trace): 1 - busy / length, over the slice from the first traced
call's start to the last one's end, so the host's planning, roll-up and
dispatch between device programs count as idle; busy is averaged over
the devices used.  Nothing to read where the trace dropped records."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or not red["complete"] or red["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
