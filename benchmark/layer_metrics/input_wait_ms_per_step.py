"""Input layer (the benchmark's seeded batch feeder, which stands where
``data/loader.py`` stands): host time the step loop spends getting the
next batch onto the device, per step. Host clock."""


def read(ctx):
    w = ctx["window"]
    return w["input_wait_s"] / w["steps"] * 1e3
