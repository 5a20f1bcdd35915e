"""scatter_share: percent of the device's busy time in the window spent in
scatters (``.at[].add/min/max/set``, ``segment_sum``): op events whose
instruction is a ``scatter``, or a fusion that runs one (classed by the HLO
of the programs that ran, which the trace carries: ``harness/hlo.py``). On a
TPU v5 lite these are ``kind=kCustom`` fusions, whose opcodes only the HLO
shows."""


def read(run):
    from harness.layers import share_of_busy

    return share_of_busy(run, lambda instr: "scatter" in instr.ops)
