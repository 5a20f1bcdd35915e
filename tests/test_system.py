"""End-to-end system test: the summarize driver runs as a user would run it
(in-process via its main(argv)) on the CPU."""

import numpy as np


def test_summarize_driver_end_to_end():
    from repro.launch.summarize import main

    res = main(["--dataset", "ego-facebook", "--scale", "0.05",
                "--k-frac", "0.3", "--T", "5"])
    assert res["relative_size"] <= 0.3 + 1e-6
    assert np.isfinite(res["re1"])
