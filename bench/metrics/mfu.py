"""Useful model operations of every tick that ended in the window, over
(window seconds x the chip's bf16 peak), in %.  Useful: 2 per layer
weight per token processed (prompt tokens prefilled and tokens
decoded), the vocabulary head only where logits are taken, attention
over the live context."""


def read(run):
    if run.peaks is None:
        return None
    fl = sum(run.model.decode(t.decode)[0] + run.model.prefill(t.prefill)[0]
             for t in run.window_ticks())
    chips = run.cell.chips
    return 100.0 * fl / (run.seconds * chips * run.peaks["bf16_flops_s"])
