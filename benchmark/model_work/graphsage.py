"""Operations and compulsory bytes of one GraphSAGE training epoch, from
the cell's shapes alone (what `work.py`'s head says of counting holds).

A graph layer is two products, self and neighbour (`d_in x d_out` each;
under `use_pp` the first layer is one product over concat(x, agg x)), and
a mean aggregation of the layer's INPUT width over the E directed edges:
2*E*F forward, 2*E*F backward (its transpose applied to the cotangent). A
layer of the dense tail is one product. A linear is 2*N*in*out forward,
the same for the weight gradient and, where its input carries a gradient,
for the input gradient. The first aggregation is left out under `use_pp`:
it is computed once in set-up, not in the step.
"""

from benchmark.work import aggregation_min_bytes


def in_step_aggregations(layer_sizes, n_linear: int, use_pp: bool) -> list:
    """Widths of the aggregations the step itself performs."""
    n_graph = len(layer_sizes) - 1 - n_linear
    return [layer_sizes[i] for i in range(n_graph)
            if not (use_pp and i == 0)]


def linear_flops(n_nodes: int, layer_sizes, n_linear: int,
                 use_pp: bool) -> int:
    n_layers = len(layer_sizes) - 1
    n_graph = n_layers - n_linear
    total = 0
    for i in range(n_layers):
        d_in, d_out = layer_sizes[i], layer_sizes[i + 1]
        if i < n_graph and use_pp and i == 0:
            mats = [2 * d_in]          # one product over concat(x, agg x)
        elif i < n_graph:
            mats = [d_in, d_in]        # self and neighbour products
        else:
            mats = [d_in]
        passes = 2 if i == 0 else 3    # the input features need no gradient
        total += sum(2 * n_nodes * m * d_out for m in mats) * passes
    return total


def aggregation_flops(n_edges: int, widths) -> int:
    return sum(2 * n_edges * w * 2 for w in widths)   # forward + backward


def epoch_work(facts: dict, flags: dict, itemsize: int) -> dict:
    """`facts`: the cell as the program built it (`n_nodes`, `n_edges`,
    `layer_sizes`); `flags`: the program's resolved flags (`n_linear`,
    `use_pp`); `itemsize`: bytes of an activation."""
    n_nodes, n_edges = facts["n_nodes"], facts["n_edges"]
    layer_sizes = facts["layer_sizes"]
    n_linear, use_pp = flags["n_linear"], flags["use_pp"]
    widths = in_step_aggregations(layer_sizes, n_linear, use_pp)
    agg = aggregation_flops(n_edges, widths)
    lin = linear_flops(n_nodes, layer_sizes, n_linear, use_pp)
    return {
        "flops": agg + lin, "aggregation_flops": agg, "linear_flops": lin,
        # one entry per aggregation pass of the step, forward and backward
        "aggregation_passes": [
            {"flops": 2 * n_edges * w,
             "min_bytes": aggregation_min_bytes(n_nodes, n_edges, w,
                                                itemsize)}
            for w in widths for _ in ("forward", "backward")],
    }
