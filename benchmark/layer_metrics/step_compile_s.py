"""What the train step costs before it is steady: the pp precompute
(compile and run) plus the warm-up dispatches' wall time less the steady
time of as many epochs. In a checkout's first run this is compilation; in
later runs it is the load from the compile cache."""


def read(ctx):
    setup = ctx["setup"]
    steady = ctx["warm_epochs"] * ctx["epoch_s"]
    return setup.get("pp_precompute", 0.0) + max(
        ctx["warm_dispatch_s"] - steady, 0.0)
