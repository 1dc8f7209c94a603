"""``sim8x8-batch`` is admitted: it resolves from ``BENCHMARK.json`` (which
shadows its entry in ``candidates.json``) with the admitted bound and
metrics, its configuration lists every key its file cuts, and
``float-batch`` keeps the metric set the benchmark admitted it with."""
import json

from benchmarks.chip import harness

BENCHMARK = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _names(metrics):
    return {m["name"] for m in metrics}


def test_sim_cell_resolves_from_the_benchmark_with_its_metrics():
    assert "sim8x8-batch" in {w["name"] for w in BENCHMARK["workloads"]}
    cell = harness.load_cell("sim8x8-batch")
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "qwen2.5-3b-sim8x8", "batch", 1)
    e2e = {m["name"]: m for m in cell.end_to_end}
    assert set(e2e) == {"out_tok_s", "setup_s"}
    assert e2e["out_tok_s"]["bound"] == 0.02  # the benchmark's, not 0.05
    assert _names(cell.per_layer) == {"device_idle.batch",
                                      "decode_step_ms.batch", "mfu.batch",
                                      "fabric_roofline"}
    assert cell.limits == {"max_logit_gap": 0.5, "fabric_calls_missing": 0}
    assert cell.config["imc_fabric"]["mode"] == "sim"
    assert cell.config["fabric_kernel"] == "bitplane_mac"


def test_sim_configuration_lists_every_key_its_file_reduces():
    conf = {c["name"]: c for c in BENCHMARK["configs"]}["qwen2.5-3b-sim8x8"]
    cfg = json.loads((harness.ROOT / conf["file"]).read_text())
    assert set(conf["reduced"]) == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 4


def test_float_cell_metrics_are_unchanged():
    cell = harness.load_cell("float-batch")
    assert _names(cell.end_to_end) == {"out_tok_s", "setup_s"}
    assert _names(cell.per_layer) == {"device_idle.batch",
                                      "decode_step_ms.batch", "mfu.batch"}
    assert cell.config.get("imc_fabric") is None
