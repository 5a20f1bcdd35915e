"""sort_share: percent of the device's busy time in the window spent in
sorts: op events whose instruction is a ``sort``, or a fusion that runs one
(classed by the HLO of the programs that ran, which the trace carries:
``harness/hlo.py``). On a TPU v5 lite the per-round sorts of the pair and
group tables are top-level ``sort`` instructions of ``jit__local_chunk``."""


def read(run):
    from harness.layers import share_of_busy

    return share_of_busy(run, lambda instr: "sort" in instr.ops)
