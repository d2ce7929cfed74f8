"""Host graph preparation: loading or generating the graph, partitioning,
clustering, sharding, saving (the harness's one-off `prepare` call in a
checkout's first run, plus `run()`'s `graph_partition`, which loads the
artifact). Source: the program's own host clocks."""


def read(ctx):
    setup = ctx["setup"]
    if "graph_partition" not in setup:
        return None
    return setup["graph_partition"] + setup.get("prepare_s", 0.0)
