"""The serving cells' correctness check, driven end to end on the CPU at
a small size with the look for a chip skipped: a sound run comes out
correct, and a run whose timed path is broken underneath comes out not
correct. The control (bfloat16 attention), put through the cell's own
comparison in the program's place, comes out not correct."""
import io
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import tinydoc  # noqa: E402
from lib.harness import main  # noqa: E402

OFFLINE = "ouro-2.6b-kv-f32.offline-chat"
RATE = "ouro-2.6b-kv-f32.chat-rate"


@pytest.fixture(scope="module")
def doc(tmp_path_factory):
    return tinydoc.build(tmp_path_factory.mktemp("doc"))


def run(doc, workload, seed=11, control=False, seconds=1.5):
    out, err = io.StringIO(), io.StringIO()
    rc = main(["--workload", workload, "--seed", str(seed),
               "--seconds", str(seconds), "--trace", "0"],
              require_accelerator=False, doc_root=doc, use_cache=False,
              control=control, out=out, err=err)
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def fresh_steps(monkeypatch):
    """Step programs are cached per process; a broken one must be built
    anew and must not leak into later tests."""
    import repro.serving.offline_harness as oh
    monkeypatch.setattr(oh, "_STEP_CACHE", {})
    return oh


@pytest.mark.parametrize("workload", [OFFLINE, RATE])
def test_sound_run_is_correct(doc, workload):
    res = run(doc, workload)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= 1e-6
    assert res["checks"]["kv_rows_err"]["value"] == 0
    metric = "gen_tokens_per_s" if workload == OFFLINE else "itl_p95_s"
    assert res["metrics"][metric]["value"] > 0


def test_token_altered_where_produced_is_caught(doc, fresh_steps):
    oh = fresh_steps
    orig = oh._decode_step_fn

    def broken(*a, **k):
        fn = orig(*a, **k)

        def step(*args):
            kp, vp, tok = fn(*args)
            return kp, vp, oh.jnp.where(tok >= 0, (tok + 1) % 16, tok)
        return step

    import unittest.mock as mock
    with mock.patch.object(oh, "_decode_step_fn", broken):
        res = run(doc, OFFLINE)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > 1e-3


def test_kv_append_that_leaves_the_pool_unchanged_is_caught(doc,
                                                            fresh_steps,
                                                            monkeypatch):
    oh = fresh_steps
    monkeypatch.setattr(oh, "kv_append_ref", lambda pool, rows, vals: pool)
    res = run(doc, OFFLINE)
    assert res["correct"] is False


def test_half_the_batch_left_out_is_caught(doc, fresh_steps, monkeypatch):
    oh = fresh_steps
    orig = oh.slab_decode_attention_window_ref

    def half(q, k_pool, v_pool, starts, lens, **kw):
        out = orig(q, k_pool, v_pool, starts, lens, **kw)
        keep = (oh.jnp.arange(out.shape[0]) % 2 == 0)[:, None, None]
        return oh.jnp.where(keep, out, 0.0)

    monkeypatch.setattr(oh, "slab_decode_attention_window_ref", half)
    res = run(doc, OFFLINE)
    assert res["correct"] is False


def test_heads_left_out_of_attention_are_caught(doc, fresh_steps,
                                                monkeypatch):
    # an attention that computes query head 0 alone: the output head
    # reads every head, so the served tokens go wrong
    oh = fresh_steps
    orig = oh.slab_decode_attention_window_ref

    def head0(q, k_pool, v_pool, starts, lens, **kw):
        out = orig(q, k_pool, v_pool, starts, lens, **kw)
        keep = (oh.jnp.arange(out.shape[1]) == 0)[None, :, None]
        return oh.jnp.where(keep, out, 0.0)

    monkeypatch.setattr(oh, "slab_decode_attention_window_ref", head0)
    res = run(doc, OFFLINE)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > 1e-3


def test_kv_rows_of_one_head_left_unwritten_are_caught(doc, fresh_steps,
                                                       monkeypatch):
    # appends that write every head but the last: the pool's rows differ
    # from the reference's wherever a token was appended
    oh = fresh_steps
    orig = oh.kv_append_ref

    def all_but_last(pool, rows, vals):
        keep = (oh.jnp.arange(vals.shape[1]) < vals.shape[1] - 1)
        old = pool[oh.jnp.clip(rows, 0, pool.shape[0] - 1)]
        return orig(pool, rows, oh.jnp.where(keep[None, :, None], vals, old))

    monkeypatch.setattr(oh, "kv_append_ref", all_but_last)
    res = run(doc, OFFLINE)
    assert res["correct"] is False
    assert res["checks"]["kv_rows_err"]["value"] > 0


def test_control_fails_the_limit(tmp_path):
    # every request the window completes goes into the sample, so that
    # bfloat16 has some thousands of positions to put a token first on;
    # the control's gap goes through the cell's own comparison
    doc = tinydoc.build(tmp_path, check_requests=10_000)
    res = run(doc, OFFLINE, control=True, seconds=4)
    assert res["correct"] is False
    check = res["checks"]["logit_gap"]
    assert check["value"] > check["limit"]
    assert all(c["value"] <= c["limit"] for name, c in res["checks"].items()
               if name != "logit_gap")


def test_no_accelerator_means_no_result(doc):
    out, err = io.StringIO(), io.StringIO()
    rc = main(["--workload", OFFLINE, "--seed", "1", "--seconds", "1"],
              doc_root=doc, use_cache=False, out=out, err=err)
    assert rc != 0 and out.getvalue() == ""


def test_without_the_program_it_exits_without_a_result(tmp_path):
    import shutil
    import subprocess
    root = HERE.parents[1]
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        OFFLINE, "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120, env={"PATH": "/usr/bin:/bin",
                                         "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""
