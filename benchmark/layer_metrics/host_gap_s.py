"""Device idle time per epoch while the host ran `Trainer.fit`'s own
Python: the traced window's idle gaps whose label names a host span of
the program (`host: fit/...`, or `host: step` where the program opens
no finer one), all but `fit/wait`, in which the host only waits for the
device. Nothing to read where no gap carries such a label."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    own = [(label, seconds) for label, seconds in trace.get("idle_gaps", [])
           if label == "host: step" or label.startswith("host: fit/")]
    if not own:
        return None
    return sum(seconds for label, seconds in own
               if not label.startswith("host: fit/wait")
               ) / ctx["epochs_traced"]
