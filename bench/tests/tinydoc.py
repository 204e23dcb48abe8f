"""A small document root for CPU tests: the real configuration and
traffic files with their scale cut so that a run fits a test."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def build(tmp: Path, check_requests: int = 4) -> Path:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {"ouro-2.6b-kv-f32": "serve.json",
             "memcached-fleet-1k": "fleet.json"}
    for c in bench["configs"]:
        c["file"] = files[c["name"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    s = json.loads((BENCH / "configs" / "ouro-2.6b-kv-f32.json").read_text())
    s.update(num_key_value_heads=2, num_attention_heads=2, head_dim=16,
             vocab_size=16)
    s["serving"] = {"max_batch": 4, "min_class": 128, "max_class": 512,
                    "pool_tokens": 4 * (128 + 256 + 512)}
    (tmp / "serve.json").write_text(json.dumps(s))
    f = json.loads((BENCH / "configs" / "memcached-fleet-1k.json")
                   .read_text())
    f.update(tenants=8, pages_total=48, check_every=16,
             min_items_between_refits=32, arbitrate_every_ops=400)
    (tmp / "fleet.json").write_text(json.dumps(f))
    traffic = tmp / "bench" / "traffic"
    traffic.mkdir(parents=True)
    for name in ("offline-chat", "chat-rate"):
        t = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        t["prompt"].update(median=60, max=300)
        t["output"].update(median=24, max=150)
        t.update(block=8, requests=4096, check_requests=check_requests)
        if t["arrival"]["kind"] == "poisson":
            t["arrival"]["rate_per_s"] = 40.0
        (traffic / f"{name}.json").write_text(json.dumps(t))
    for name in ("phased-drift",):
        t = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        t.update(sets_per_tenant_round=48, period_rounds=4, warmup_rounds=2,
                 check_frontiers=16)
        (traffic / f"{name}.json").write_text(json.dumps(t))
    return tmp
