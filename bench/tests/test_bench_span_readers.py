"""The readers of the host-time metrics added with the executor's own
spans: each sums its span's durations over the window and divides by the
answered samples, and finds nothing where the window holds no such span
(a program without the span, as before it was added)."""
import pytest

from bench import cell as C

READERS = {
    "executor.pack_wait_ms_per_sample.closed": "pipeline.pack_wait",
    "executor.host_eval_ms_per_sample.closed": "executor.host_eval",
    "executor.stats_ms_per_sample.closed": "executor.stats",
    "sim.compile_ms_per_sample.closed": "executor.compile",
}


def _ctx(spans, samples_done=8):
    return C.Context(setup_s=1.0, window_s=2.0, samples_done=samples_done,
                     requests_done=samples_done // 4, counters={}, spans=spans,
                     trace=None, flops_per_sample=0, peak=None)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_sums_its_span_per_sample(metric):
    span = READERS[metric]
    spans = [{"name": span, "ts": 0.0, "dur": 3000.0},       # 3 ms
             {"name": span, "ts": 5000.0, "dur": 1000.0},    # 1 ms
             {"name": "pipeline.pack", "ts": 0.0, "dur": 9e6}]
    assert C.metric_reader(metric)(_ctx(spans)) == pytest.approx(4.0 / 8)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_without_its_span(metric):
    spans = [{"name": "pipeline.pack", "ts": 0.0, "dur": 5000.0}]
    assert C.metric_reader(metric)(_ctx(spans)) is None
    span = {"name": READERS[metric], "ts": 0.0, "dur": 5000.0}
    assert C.metric_reader(metric)(_ctx([span], samples_done=0)) is None


def test_readers_are_listed_for_the_closed_cell():
    names = {m["name"] for m in C.resolve("mnist_rnn.closed").per_layer}
    assert set(READERS) <= names
