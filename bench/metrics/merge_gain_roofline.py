"""merge_gain_roofline: percent of the chip's roofline that one merge-gain
call reaches: the larger of its operations over the bf16 peak and its bytes
over the HBM bandwidth (both counted from the shapes by
``harness/roofline.py``, so every backend is read against the same work),
over its device time (``merge_gain_ms``). The bound that applies is printed
with the run's notes."""

import importlib.util
import os


def read(run):
    from harness import roofline

    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "merge_gain_ms.py")
    spec = importlib.util.spec_from_file_location("merge_gain_ms", here)
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    t_ms = ms.read(run)
    if not t_ms:
        return None
    g, c, u = run.merge_gain["shape"]
    share, bound = roofline.roofline_share(
        roofline.merge_gain_flops(g, c, u), roofline.merge_gain_bytes(g, c, u),
        t_ms * 1e-3, run.peaks())
    run.merge_gain["roofline_bound"] = bound
    return share
