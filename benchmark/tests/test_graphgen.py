"""The benchmark's copy of the data set generator against the program's:
the reference may import nothing of the program, so `graphgen.py` carries
`synthetic_graph` + `finalize` itself, and the two have to make the same
graph bit for bit, or the reference trains another one than the program."""

import numpy as np
import pytest

from benchmark import graphgen


@pytest.mark.parametrize("dataset", ["synthetic:1500:12:32:8",
                                     "synthetic:1200:8:16:5:ml"])
def test_copy_agrees_with_the_programs_generator(dataset):
    from pipegcn_tpu.graph import load_data

    theirs = load_data(dataset, "")
    shape = graphgen.parse_dataset(dataset)
    ours = graphgen.synthetic_graph(seed=0, **shape)
    n = shape["num_nodes"]
    assert theirs.num_nodes == n
    # the same edge set (the program keeps its edges in another order)
    key = lambda s, d: np.sort(np.asarray(d, np.int64) * n
                               + np.asarray(s, np.int64))  # noqa: E731
    np.testing.assert_array_equal(key(theirs.src, theirs.dst),
                                  key(ours["src"], ours["dst"]))
    for name in ("feat", "label", "train_mask"):
        np.testing.assert_array_equal(np.asarray(theirs.ndata[name]),
                                      ours[name])


def test_neighbour_table_refuses_an_edge_set_that_is_not_symmetric():
    with pytest.raises(ValueError):
        graphgen.build_ell(np.array([0, 1]), np.array([1, 2]), 3)
