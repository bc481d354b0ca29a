"""A benchmark root at tiny width for CPU tests of the harness.

``make_root(path)`` writes ``BENCHMARK.json`` and two cells, one per
family, whose configurations keep the real ones' structure (bf16, tied or
untied head, sliding window) at a width a test run can hold, and copies
the metric readers and the peaks table.  ``on_cpu(monkeypatch)`` lets the
harness run on the CPU: the chip check and the peaks lookup are stubbed,
which the benchmark itself never does.
"""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SSM_CELL = "tiny-ssm.wake_zipf"
HYBRID_CELL = "tiny-hybrid.docs_closed"
#: between the program's widest gap and the fp8 control's at these sizes
#: (CPU, 6 seeds each: SSM program 0.0, control 0.030-0.151; hybrid
#: program 0.002-0.023, control 0.177-0.377)
GAP_LIMIT = {SSM_CELL: 0.01, HYBRID_CELL: 0.06}


#: the wake mix (open loop onto hibernated tenants, Zipf popularity), as the
#: mamba2-130m.wake_zipf cell would run it once its rate is set on the chip
WAKE_MIX = {
    "config": "mamba2-130m", "traffic": "wake_zipf", "tenants": 8,
    "popularity": {"zipf_s": 1.0},
    "arrivals": {"kind": "open_poisson", "rate_per_s": 3.2},
    "prompt_len": {"128": 0.7, "512": 0.3},
    "output_len": {"2": 0.5, "8": 0.5},
    "policy": {"keep_warm_s": 5.0, "workers": 4},
    "setup": {"descend_to": "hibernated"},
    "warm_decode_batches": [1, 2, 3, 4, 5, 6, 7, 8],
    "check": {"sample_tokens": 240, "logit_gap_limit": 0.2},
}
#: the document mix (closed loop, 512- and 2048-token prompts on warm
#: tenants), as the hymba-1.5b-L8.docs_closed cell would run it
DOCS_MIX = {
    "config": "hymba-1.5b-L8", "traffic": "docs_closed", "tenants": 4,
    "popularity": {"zipf_s": 0.0},
    "arrivals": {"kind": "closed", "clients_per_tenant": 1},
    "prompt_len": {"512": 0.5, "2048": 0.5},
    "output_len": {"4": 0.5, "16": 0.5},
    "policy": {"keep_warm_s": 600.0, "workers": 4},
    "setup": {"descend_to": None},
    "warm_decode_batches": [1],
    "check": {"sample_tokens": 240, "logit_gap_limit": 0.13},
}


def _load(rel):
    return json.loads((REPO / rel).read_text())


def make_root(root: Path) -> Path:
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "workloads").mkdir()
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics")
    shutil.copy(REPO / "bench" / "peaks.json", root / "bench" / "peaks.json")
    ssm = _load("bench/configs/mamba2-130m.json")
    ssm.update(name="tiny-ssm", num_layers=2, d_model=256, vocab_size=512,
               ssm={"state_dim": 16, "head_dim": 16, "expand": 2,
                    "chunk_size": 32, "conv_width": 4})
    hyb = _load("bench/configs/hymba-1.5b-L8.json")
    hyb.update(name="tiny-hybrid", num_layers=2, d_model=64, num_heads=4,
               num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
               sliding_window=24,
               ssm={"state_dim": 8, "head_dim": 16, "expand": 2,
                    "chunk_size": 16, "conv_width": 4})
    for c in (ssm, hyb):
        (root / "bench" / "configs" / f"{c['name']}.json").write_text(
            json.dumps(c))
    wake = dict(WAKE_MIX, config="tiny-ssm", tenants=3,
                arrivals={"kind": "open_poisson", "rate_per_s": 6.0},
                prompt_len={"16": 0.5, "32": 0.5},
                output_len={"2": 0.5, "4": 0.5},
                policy={"keep_warm_s": 0.3, "workers": 2},
                warm_decode_batches=[1, 2], check=dict(WAKE_MIX["check"]))
    docs = dict(DOCS_MIX, check=dict(DOCS_MIX["check"]))
    docs.update(config="tiny-hybrid", tenants=2,
                prompt_len={"16": 0.5, "40": 0.5},
                output_len={"2": 0.5, "6": 0.5})
    for cell, mix in ((SSM_CELL, wake), (HYBRID_CELL, docs)):
        mix["check"].update(sample_tokens=40, logit_gap_limit=GAP_LIMIT[cell])
    bench = _load("BENCHMARK.json")
    bench["configs"] = [
        dict(bench["configs"][0], name="tiny-ssm",
             file="bench/configs/tiny-ssm.json"),
        dict(bench["configs"][0], name="tiny-hybrid",
             file="bench/configs/tiny-hybrid.json")]
    bench["workloads"] = [
        dict(bench["workloads"][0], name=SSM_CELL, config="tiny-ssm",
             traffic="wake_zipf"),
        dict(bench["workloads"][0], name=HYBRID_CELL, config="tiny-hybrid",
             traffic="docs_closed")]
    # the wake layer's metric, read in the wake cell alone
    bench["per_layer"].append({
        "name": "wake_ms.p50", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "wake", "moves": "tokens_per_s",
        "workloads": [SSM_CELL]})
    (root / "bench" / "workloads" / f"{SSM_CELL}.json").write_text(
        json.dumps(wake))
    (root / "bench" / "workloads" / f"{HYBRID_CELL}.json").write_text(
        json.dumps(docs))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def on_cpu(monkeypatch):
    import jax
    from bench import harness
    monkeypatch.setattr(harness, "require_chip",
                        lambda spec: jax.devices()[0])
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind, root: {"bf16_flops_per_s": 1e12})
    return harness
