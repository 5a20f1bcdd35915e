"""sparsify_ms: device milliseconds per job of the Sect. 3.2.4 drop-to-k
selection in the finalize (``core/sparsify.py::further_sparsify``): the ops of
``jit__local_finalize`` under the program's named scope ``sparsify``
(``harness/program_layers.py``)."""


def read(run):
    from harness.program_layers import sparsify_ms

    return sparsify_ms(run)
