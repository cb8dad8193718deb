"""Share of slot-ticks that held a request over the window, from the
program's own counters: ``Server.slot_ticks / (Server.ticks * batch)``,
in %."""


def read(run):
    c = run.counters
    if "ticks0" not in c:
        return None
    ticks = c["ticks1"] - c["ticks0"]
    if ticks <= 0:
        return None
    return 100.0 * (c["slot_ticks1"] - c["slot_ticks0"]) / (ticks * run.batch)
