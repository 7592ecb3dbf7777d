"""The zamba2 family: its counts pinned to numbers worked by hand, its cell
`zamba2.batch` found by name in the checkout, and a tiny zamba2 cell run
through the whole harness on the CPU from new files alone (a checkout
under tmp_path holds its configuration, traffic and BENCHMARK.json; the
family, reference, `bench/drivers/serve.py` and readers come from this
checkout)."""
import dataclasses
import json

import pytest

from bench.lib.cell import load_cell, load_module
from bench.tests.conftest import ROOT, TINY

CELL = "zamba2.batch"
METRICS = ("decode_step_ms.zamba2", "step_hbm_roofline.zamba2",
           "device_idle_share.zamba2")


def _spec():
    cfg = json.loads((ROOT / "bench" / "configs" / "zamba2-7b-instruct.json")
                     .read_text())
    family = load_module("families", "zamba2")
    return family, family.spec(cfg)


def test_counts_are_worked_by_hand():
    """A Mamba2 layer: in_proj 3584 x (2 x 7168 + 2 x 2 x 64 + 112) =
    52,699,136, out_proj 7168 x 3584 = 25,690,112, conv 7424 x (4 + 1),
    A, D, dt bias 3 x 112, gated norm 7168, input norm 3584: 78,437,456.
    A shared block: q/k/v 7168 x 7168 x 3 = 154,140,672, o 7168 x 3584 =
    25,690,112, gate/up 3584 x 28672 = 102,760,448, down 14336 x 3584 =
    51,380,224, norms 7168 + 3584: 333,982,208.  A use: linear 3584^2 =
    12,845,056, adapter 3584 x 128 + 128 x 28672: 16,973,824."""
    family, s = _spec()
    assert family.layer_params(s) == {
        "mamba2": 78_437_456, "shared_block": 333_982_208,
        "use": 16_973_824, "embedding": 114_688_000}
    # 2 x (41 x 78,389,248 + 6 x (333,971,456 + 16,973,824) + 3584 x 32000)
    assert family.matmul_flops_per_token(s) == 10_868_637_696.0
    # 41 x (2 x 7424 x 4 + 5 x 112 x 64 x 64) + 6 x 4 x 32 x 224 x 512
    assert family.mixer_flops(s, 511) == 184_559_616.0
    assert family.sequence_flops(s, 512) == 5_636_732_485_632.0
    # weights 2 x 5,436,359,888; KV 6 x 16 x 1024 x 32 x 224 x 2 x 2;
    # state 41 x 16 x (112 x 64 x 64 x 4 + 3 x 7424 x 2) x 2
    assert family.step_bytes(s, 16, 1023) == \
        10_872_719_776 + 2_818_572_288 + 2_465_972_224
    # fewer live sequences and positions move fewer bytes
    assert family.step_bytes(s, 3, 100) < family.step_bytes(s, 16, 100) \
        < family.step_bytes(s, 16, 1023)


def test_cell_loads_by_name():
    cell = load_cell(CELL, ROOT)
    assert cell.family.__file__.endswith("bench/families/zamba2.py")
    s = cell.family.spec(cell.config)
    assert (s.n_layers, s.hybrid_layer_ids) == (41, (6, 11, 17, 23, 29, 35))
    assert [r for r, _ in cell.family.stage_layout(s)] == [1, 2, 1]
    t = cell.traffic
    assert t["arrival"]["kind"] == "closed"
    assert t["prompt"]["max"] + t["output"]["max"] \
        <= cell.config["serving"]["max_len"]
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "itl_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(METRICS)
    load_module("reference", cell.config["reference"], ROOT)


# ------------------------------------------------------------ tiny cell
def tiny_config() -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / "zamba2-7b-instruct.json")
                     .read_text())
    ids = [1, 3, 5, 7]
    cfg.update(
        name="zamba2-tiny", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, num_query_groups=4, attention_head_dim=32,
        attention_hidden_size=128, kv_channels=16, ffn_hidden_size=128,
        intermediate_size=128, adapter_rank=8, mamba_d_state=16,
        n_mamba_heads=4, mamba_headdim=32, vocab_size=256,
        num_hidden_layers=9, hybrid_layer_ids=ids,
        layers_block_type=["hybrid" if i in ids else "mamba"
                           for i in range(9)],
        serving={"batch": 4, "max_len": 40})
    return cfg


def tiny_checkout(root):
    for kind in ("configs", "traffic"):
        (root / "bench" / kind).mkdir(parents=True)
    cfg = tiny_config()
    (root / "bench" / "configs" / "zamba2-tiny.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny_batch_1k.json").write_text(
        (TINY / "bench" / "traffic" / "tiny_batch.json").read_text())
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "zamba2-tiny", "source": cfg["source"],
                     "file": "bench/configs/zamba2-tiny.json",
                     "reduced": cfg["reduced"], "why": "test"}]
    b["workloads"] = [{"name": "zamba2.tiny", "config": "zamba2-tiny",
                       "traffic": "tiny_batch_1k", "chips": 1,
                       "why": "test"}]
    b["end_to_end"] = [dict(m, workloads=["zamba2.tiny"])
                       for m in b["end_to_end"] if m.get("workloads")
                       and CELL in m["workloads"]] + [
        m for m in b["end_to_end"] if m["name"] == "setup_s"]
    b["per_layer"] = [dict(m, workloads=["zamba2.tiny"])
                      for m in b["per_layer"] if m["name"] in METRICS]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.fixture
def checkout(tmp_path):
    return tiny_checkout(tmp_path / "checkout")


def test_tiny_cell_runs_correct(run_tiny, checkout):
    r = run_tiny("zamba2.tiny", root=checkout, seed=2**33 + 5)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert {"tokens_per_s", "itl_p95_ms", "setup_s"} <= set(r["metrics"])


class _OneMsDecode:
    """The CPU's trace has no module line: a decode step of 1 ms a call."""

    def __init__(self, calls):
        self.calls = calls

    def module_time(self, pattern):
        return self.calls * 1e-3, self.calls


def test_tiny_cell_traced_reads_every_metric(run_tiny, checkout,
                                             monkeypatch):
    from bench.lib import device, harness, program_spans
    runs, read = [], harness._read_metrics

    def keep(run, entries):
        runs.append(run)
        return read(run, entries)

    monkeypatch.setattr(harness, "_read_metrics", keep)
    r = run_tiny("zamba2.tiny", root=checkout, trace=True)
    assert r["correct"], r["checks"]
    assert "device_idle_share.zamba2" in r["metrics"]
    (run,) = runs
    steps = program_spans.log(run).named("engine/step")
    # a lockstep wave runs on after its requests finish: live may be 0
    live = [s.end_args["live"] for s in steps]
    assert max(live) == 4 and min(live) >= 0
    # the roofline share from the steps' own live slots and positions,
    # with the CPU's missing peaks and module time given as a v5e's
    peaks = device.peaks("TPU v5 lite")
    run = dataclasses.replace(run, peaks=peaks,
                              trace=_OneMsDecode(len(steps)))
    share = load_module("metrics", "step_hbm_roofline.zamba2", checkout)
    need = sum(run.cell.family.step_bytes(run.spec, s.end_args["live"],
                                          s.args["step"]) for s in steps)
    assert share.read(run) == pytest.approx(
        need / peaks["hbm_bytes_per_s"] / len(steps) / 1e-3 * 100)


def test_tiny_cell_altered_token_is_not_correct(run_tiny, checkout,
                                                monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import decode_step
    from repro.serving import engine

    def altered(params, cfg, cache, tokens, pos):
        logits, new = decode_step(params, cfg, cache, tokens, pos)
        bump = jnp.where(pos % 3 == 1, 1e4, 0.0).astype(logits.dtype)
        return logits.at[:, 5].add(bump), new

    def apply(drv):
        engine._decode = jax.jit(altered, static_argnums=1)

    monkeypatch.setattr(engine, "_decode", engine._decode)
    r = run_tiny("zamba2.tiny", root=checkout, seconds=0.3,
                 after_setup=apply)
    assert not r["correct"], r["checks"]
