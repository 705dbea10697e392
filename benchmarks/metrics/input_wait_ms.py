"""Input (``horovod_tpu/data.py``): host milliseconds an optimizer step
waited for its batch from ``prefetch_to_device`` over ``BackgroundLoader``."""


def read(run):
    return 1e3 * sum(run.input_wait_s) / run.steps
