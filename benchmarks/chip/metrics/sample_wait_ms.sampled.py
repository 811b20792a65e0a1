"""Host milliseconds per step that the training loop waited for its
next minibatch from the program's prefetcher (the harness's clock
around ``next``)."""


def read(run):
    steps = run.layer.get("steps")
    if not steps or "sample_wait_s" not in run.layer:
        return None
    return 1e3 * run.layer["sample_wait_s"] / steps
