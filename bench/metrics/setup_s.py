"""setup_s: seconds from process start to the first timed call: daemon
start, tracing, pricing, loading programs from the compile cache, inputs
and warm-up."""


def read(run):
    return run.record.get("setup_s")
