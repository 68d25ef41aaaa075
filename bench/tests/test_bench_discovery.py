"""A later change adds a configuration, a traffic mix or a metric as new
files and new entries in BENCHMARK.json: the harness finds them by name,
and no file it already has needs an edit."""
import hashlib
import json
import shutil

from bench import cell as C

REPO_BENCH = C.ROOT / "bench"


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_config_and_metric_are_found(tmp_path):
    shutil.copytree(REPO_BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((C.ROOT / "BENCHMARK.json").read_text())
    before = _digests(tmp_path / "bench")

    # new files only
    cfg = json.loads((REPO_BENCH / "configs" / "mnist_rnn.json").read_text())
    cfg["name"] = "mnist_rnn_b"
    (tmp_path / "bench" / "configs" / "mnist_rnn_b.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "fanout.json").write_text(json.dumps(
        {"clients": 8, "samples": 2, "queue_depth": 64, "max_batch": 16}))
    (tmp_path / "bench" / "metrics" / "serving.answered.py").write_text(
        "def read(ctx):\n    return float(ctx.requests_done) or None\n")
    (tmp_path / "bench" / "metrics" / "requests_per_s.py").write_text(
        "def read(ctx):\n    return ctx.requests_done / ctx.window_s\n")
    # new entries only
    spec["configs"].append({"name": "mnist_rnn_b", "source": "https://example.org",
                            "file": "bench/configs/mnist_rnn_b.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "mnist_rnn_b.fanout",
                              "config": "mnist_rnn_b", "traffic": "fanout",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "serving.answered", "unit": "req", "better": "higher",
                              "source": "program_counter", "layer": "serving",
                              "moves": "requests_per_s", "workloads": ["mnist_rnn_b.fanout"]})
    spec["end_to_end"].append({"name": "requests_per_s", "unit": "req/s", "better": "higher",
                               "bound": 0.1, "source": "host_clock",
                               "workloads": ["mnist_rnn_b.fanout"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = C.resolve("mnist_rnn_b.fanout", tmp_path)
    assert cell.config["name"] == "mnist_rnn_b"
    assert cell.config_dir == tmp_path / "bench" / "configs"
    assert C.reachable_sizes(cell.traffic) == [2, 4, 6, 8, 10, 12, 14, 16]
    assert cell.reference.param_shapes(cell.config)["rnn.weight_hh_l0"] == (256, 64)
    assert [m["name"] for m in cell.per_layer] == ["serving.answered"]
    assert {m["name"] for m in cell.e2e} == {"requests_per_s", "setup_s"}
    ctx = C.Context(setup_s=1.0, window_s=2.0, samples_done=6,
                    requests_done=2, counters={}, spans=[], trace=None,
                    flops_per_sample=0, peak=None)
    assert C.metric_reader("serving.answered", tmp_path)(ctx) == 2.0
    assert C.metric_reader("requests_per_s", tmp_path)(ctx) == 1.0

    after = _digests(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_listed_metric_has_a_reader():
    spec = C.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(C.metric_reader(m["name"]))
    for w in spec["workloads"]:
        cell = C.resolve(w["name"])
        assert cell.e2e and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.e2e}


def test_readers_find_nothing_in_an_empty_window():
    ctx = C.Context(setup_s=1.0, window_s=1.0, samples_done=0,
                    requests_done=0, counters={}, spans=[], trace=None,
                    flops_per_sample=0, peak=None)
    spec = C.load_spec()
    for m in spec["per_layer"]:
        assert C.metric_reader(m["name"])(ctx) is None, m["name"]
