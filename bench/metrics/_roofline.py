"""Shared arithmetic of the step rooflines: for every traced tick that
ran the program, the least time of the work the host asked of it
(``bench.cost``) over the program's device time in that tick."""

from bench import cost


def share(run, program: str, work) -> float | None:
    red = run.red
    if red is None or not run.traced_ticks or run.peaks is None:
        return None
    least = spent = 0.0
    for tk, progs in zip(run.traced_ticks, red.tick_programs):
        fl, by = work(run.model, tk)
        if program not in progs or fl == 0.0:
            continue
        least += cost.least_time(fl, by, run.peaks)
        spent += progs[program] * 1e-9
    return 100.0 * least / spent if spent > 0 else None
