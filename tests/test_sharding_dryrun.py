"""Sharding rules + a miniature multi-device dry-run.

Device-count-sensitive pieces run in SUBPROCESSES so the forced
XLA_FLAGS never leak into the main pytest process (per the dry-run
contract: only launch/dryrun.py forces fake devices).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str) -> str:
    code = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(body))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep + REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_param_spec_rules():
    out = run_py("""
    import jax, json
    from repro.models import get_model
    from repro.sharding import param_spec
    from repro.launch.mesh import make_debug_mesh
    cfg, model = get_model("mixtral-8x7b", reduced=True)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = make_debug_mesh(4, 2)
    spec = param_spec(params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(spec)[0]
    specs = {jax.tree_util.keystr(p, simple=True, separator='/'): str(s)
             for p, s in flat}
    print(json.dumps(specs))
    """)
    specs = json.loads(out)
    # attention projections: output dim on model axis (stacked layer lead)
    assert specs["layers/attn/wq"] == "PartitionSpec(None, None, 'model')"
    assert specs["layers/attn/wo"] == "PartitionSpec(None, 'model', None)"
    # moe experts: reduced mixtral has 4 experts on a 4-way data axis -> EP
    assert "'data'" in specs["layers/moe/we_in"]
    assert "'model'" in specs["layers/moe/we_in"]
    # embeddings: vocab on model
    assert specs["embedding/embed"] == "PartitionSpec('model', None)"
    # norms replicated
    assert specs["final_norm"] == "PartitionSpec()"


def test_zero_spec_adds_data_axis():
    out = run_py("""
    import jax, json
    from repro.models import get_model
    from repro.sharding import zero_spec
    from repro.launch.mesh import make_debug_mesh
    cfg, model = get_model("deepseek-7b", reduced=True)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = make_debug_mesh(4, 2)
    spec = zero_spec(params, mesh)
    flat = jax.tree_util.tree_flatten_with_path(spec)[0]
    specs = {jax.tree_util.keystr(p, simple=True, separator='/'): str(s)
             for p, s in flat}
    print(json.dumps(specs))
    """)
    specs = json.loads(out)
    # moments gain a 'data' dim beyond the param spec (ZeRO-1)
    assert "'data'" in specs["layers/attn/wq"]
    assert "'model'" in specs["layers/attn/wq"]


def test_mini_dryrun_train_and_decode_compile():
    """End-to-end miniature of launch/dryrun.py on a 4x2 debug mesh:
    lower+compile a train step and a decode step of a reduced arch with
    the production sharding rules; assert collectives exist and the HLO
    walker returns sane numbers."""
    out = run_py("""
    import jax, jax.numpy as jnp, json
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import get_model
    from repro.sharding import (param_spec, zero_spec, cache_spec,
                                to_shardings)
    from repro.launch.mesh import make_debug_mesh
    from repro.training import (AdamWConfig, TrainConfig,
                                init_train_state, make_train_step)
    from repro.training.train_step import TrainState
    from repro.training.optimizer import OptState
    from benchmarks import hlo_analysis

    cfg, model = get_model("gemma3-1b", reduced=True)
    mesh = make_debug_mesh(4, 2)
    tcfg = TrainConfig(microbatches=2, optimizer=AdamWConfig())
    step = make_train_step(model, tcfg)
    params_sh = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state_sh = jax.eval_shape(lambda p: init_train_state(p, tcfg),
                              params_sh)
    state_spec = TrainState(
        params=param_spec(params_sh, mesh),
        opt=OptState(step=P(), mu=zero_spec(params_sh, mesh),
                     nu=zero_spec(params_sh, mesh)),
        residuals=None)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 33), jnp.int32)}
    with mesh:
        fn = jax.jit(step,
                     in_shardings=(to_shardings(state_spec, mesh),
                                   {"tokens": NamedSharding(
                                       mesh, P("data", None))}),
                     donate_argnums=(0,))
        compiled = fn.lower(state_sh, batch).compile()
    walk = hlo_analysis.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    result = {"flops": walk.dot_flops,
              "coll": walk.collective_bytes,
              "kinds": walk.coll_by_kind,
              "temp": mem.temp_size_in_bytes}

    # decode step on the same mesh
    cache_sh = jax.eval_shape(lambda: model.init_cache(8, 64))
    def dstep(params, token, cache, cache_len):
        return model.decode(params, token, cache, cache_len, None)
    with mesh:
        dfn = jax.jit(dstep, in_shardings=(
            to_shardings(param_spec(params_sh, mesh), mesh),
            NamedSharding(mesh, P("data", None)),
            to_shardings(cache_spec(cache_sh, mesh), mesh),
            NamedSharding(mesh, P())), donate_argnums=(2,))
        dcomp = dfn.lower(params_sh,
                          jax.ShapeDtypeStruct((8, 1), jnp.int32),
                          cache_sh,
                          jax.ShapeDtypeStruct((), jnp.int32)).compile()
    dwalk = hlo_analysis.analyze(dcomp.as_text())
    result["decode_flops"] = dwalk.dot_flops
    print(json.dumps(result))
    """)
    res = json.loads(out.splitlines()[-1])
    assert res["flops"] > 1e6                 # trip-counted layer flops
    assert res["coll"] > 0                    # TP produces collectives
    assert "all-reduce" in res["kinds"]
    assert res["decode_flops"] > 0
    assert res["temp"] > 0


def test_cache_spec_seq_parallel():
    out = run_py("""
    import jax, jax.numpy as jnp, json
    from repro.models import get_model
    from repro.sharding import cache_spec
    from repro.launch.mesh import make_debug_mesh
    cfg, model = get_model("deepseek-7b", reduced=True)
    mesh = make_debug_mesh(4, 2)
    cache = jax.eval_shape(lambda: model.init_cache(1, 64))  # batch 1
    spec = cache_spec(cache, mesh, seq_parallel=True)
    print(json.dumps({k: str(v) for k, v in spec.items()}))
    """)
    specs = json.loads(out.splitlines()[-1])
    # batch=1 -> sequence dim carries the data axis
    assert "'data'" in specs["k"]
