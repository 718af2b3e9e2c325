"""setup_s: process start to the start of the window (weights, inputs,
`ChipSimulator` and its mapping, lowering, compilation, warm-up)."""


def read(run):
    return run.setup_s
