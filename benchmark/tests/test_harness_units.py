"""Small parts of the harness on made-up inputs: the feed's rows by
partition, the dispatch plan and which of its scans runs first, and the
prepared artifact shared between the cells of a configuration, whose HLO
text the scope join reads, and the host line `load_xplane` keeps."""

import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness


def test_rows_of_the_feed_by_partition():
    sg = SimpleNamespace(global_nid=np.array([[2, 0, -1], [1, 3, -1]]),
                         num_parts=2, n_max=2, halo_size=1,
                         edge_count=np.array([5, 4]), multilabel=False)
    state = {"params": {"w": np.ones(2)},
             "opt": {"mu": {"w": np.zeros(2)}, "nu": {"w": np.zeros(2)},
                     "step": np.int32(2)}}
    trainer = SimpleNamespace(
        sg=sg, state=state, tuning=None, _current_impl=lambda: "bucket",
        cfg=SimpleNamespace(layer_sizes=(8, 4, 3)))
    facts = harness.first_dispatch_facts(trainer, 2)
    assert facts["part_of_node"].tolist() == [0, 1, 0, 1]
    assert facts["row_of_node"].tolist() == [1, 0, 0, 1]
    assert (facts["num_parts"], facts["n_rows"], facts["n_nodes"],
            facts["n_edges"], facts["opt_step"]) == (2, 3, 4, 9, 2)


def test_dispatch_plan_is_fits_and_the_shortest_scan_goes_first():
    assert harness.dispatch_plan(10, 20, 4, 10) == [(10, 4), (14, 4),
                                                    (18, 2)]
    assert [n for _, n in harness.dispatch_plan(10, 20, 3, 5)] == [3, 2, 3,
                                                                   2]
    # a run of as many epochs as the shortest scan is that one scan
    assert harness.dispatch_plan(0, 2, 4, 10) == [(0, 2)]


def test_a_second_cell_links_the_prepared_artifact_and_owns_what_it_adds(
        tmp_path):
    src, dst = tmp_path / "artifact", tmp_path / "cell"
    (src / "g").mkdir(parents=True)
    (src / "g" / "shard.npy").write_bytes(b"rows")
    harness.link_tree(str(src), str(dst))
    assert (dst / "g" / "shard.npy").read_bytes() == b"rows"
    (dst / "g" / "tuning.json").write_text("{}")      # the cell's own
    harness.link_tree(str(src), str(dst))             # again: leaves it
    assert not (src / "g" / "tuning.json").exists()
    assert sorted(os.listdir(dst / "g")) == ["shard.npy", "tuning.json"]


def test_the_scope_join_reads_the_text_the_trainer_compiles_past_the_cache():
    """`multi_step_hlo` asks the trainer for `step_compiled_text`, which
    compiles past the persistent cache: a cache hit would name the scopes
    of whichever checkout compiled the program first."""
    asked = []

    def step_compiled_text(length):
        asked.append(length)
        return f"HloModule scan_of_{length}"

    trainer = SimpleNamespace(step_compiled_text=step_compiled_text)
    assert harness.multi_step_hlo(trainer, 4) == "HloModule scan_of_4"
    assert harness.multi_step_hlo(trainer, 1) == "HloModule scan_of_1"
    assert asked == [4, 1]
    # a trainer that cannot give it: no text, and no crash of the run
    assert harness.multi_step_hlo(SimpleNamespace(), 2) is None


@pytest.mark.parametrize("thread_line", ["python", "python3", "MainThread"])
def test_the_main_threads_host_line_is_kept_whatever_it_is_called(
        monkeypatch, thread_line):
    """The main thread's line is named by how the interpreter was started.
    What goes is the Python tracer's call events (`$file.py:12 fn`), by
    what they are; the program's spans on that line stay."""
    import jax.profiler

    from benchmark import trace_reduce as T

    def ev(name, start, dur):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                               stats=[])

    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
        name=thread_line, events=[
            ev("$profiler.py:101 start_trace", 0.0, 5.0),
            ev("fit/keys", 10.0, 50.0), ev("$<unknown> __exit__", 70.0, 1.0),
            ev("step", 5.0, 90.0)])])
    fake = SimpleNamespace(from_file=lambda path: SimpleNamespace(
        planes=[host]))
    monkeypatch.setattr(jax.profiler, "ProfileData", fake)
    raw = T.load_xplane("nowhere.xplane.pb")
    assert raw["host"] == [["fit/keys", 10.0, 50.0], ["step", 5.0, 90.0]]
