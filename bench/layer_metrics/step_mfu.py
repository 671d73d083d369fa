"""The whole step's share of the chip's peak (%): the logical work of every
token the window processed — 2·K·N integer ops per token for each DA
product (LM head per emitted token) at the int8 peak, 4·heads·hd·context
attention flops at the bf16 peak — as time at peak, over the window."""


def read(run):
    if not run.calls:
        return None
    lin, att = run.logical_ops()
    if lin + att <= 0:
        return None
    at_peak = lin / run.peaks["int8_ops"] + att / run.peaks["bf16_flops"]
    return 100.0 * at_peak / run.window_s
