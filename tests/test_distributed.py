"""Distributed: sharding rules, compressed collectives, multi-device math
equivalence. Multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test process
keeps the real single-device view (per assignment).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro import configs
from repro.distributed import sharding
from repro.launch import mesh as mesh_mod
from repro.launch import specs as specs_mod


# ---------------------------------------------------------------------------
# in-process: rule construction on a 1x1 mesh
# ---------------------------------------------------------------------------

def _mesh11():
    return mesh_mod.make_mesh((1, 1), ("data", "model"))


def test_params_shardings_cover_every_leaf():
    mesh = _mesh11()
    for arch in ("tinyllama_1_1b", "qwen2_moe_a2_7b", "mamba2_130m",
                 "recurrentgemma_9b", "minicpm3_4b", "musicgen_large"):
        cfg = configs.smoke(arch)
        params = specs_mod.params_struct(cfg)
        sh = sharding.params_shardings(params, mesh)
        n_p = len(jax.tree.leaves(params))
        n_s = len(jax.tree.leaves(
            sh, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding)))
        assert n_p == n_s


def test_rule_for_expected_specs():
    P = jax.sharding.PartitionSpec
    # column-parallel attention weight, scan-stacked [L, out, in]
    assert sharding.rule_for("['layers']['attn']['wq']['w']", 3) == \
        P(None, "model", None)
    # row-parallel
    assert sharding.rule_for("['layers']['attn']['wo']['w']", 3) == \
        P(None, None, "model")
    # MoE experts: EP over E
    assert sharding.rule_for("['layers']['moe']['gate']", 4) == \
        P(None, "model", None, None)
    # router aligns E with EP
    assert sharding.rule_for("['layers']['moe']['router']['w']", 3) == \
        P(None, "model", None)
    # embed: vocab over model
    assert sharding.rule_for("['embed']['table']", 2) == P("model", None)
    # norms replicated
    assert sharding.rule_for("['final_norm']['scale']", 1) == P()
    # Tiled-CSL words [L, mt, kt, slots, k_tb] of a column-parallel weight
    assert sharding.rule_for("['layers']['mlp']['up']['w'].words", 5) == \
        P(None, "model", None, None, None)
    # its per-tile counts [L, mt, kt] shard as its tiles do
    for leaf in ("nnz", "slabs"):
        assert sharding.rule_for(f"['layers']['mlp']['up']['w'].{leaf}",
                                 3) == P(None, "model", None)
        assert sharding.rule_for(f"['layers']['mlp']['down']['w'].{leaf}",
                                 3) == P(None, None, "model")
    # fsdp adds data on the free dim
    assert sharding.rule_for("['layers']['attn']['wq']['w']", 3,
                             fsdp=True) == P(None, "model", "data")


def test_fit_spec_drops_nondivisible():
    mesh = _mesh11()
    # degenerate 1x1 mesh: everything divides
    P = jax.sharding.PartitionSpec
    assert sharding.fit_spec(P("model", None), (7, 3), mesh) == \
        P("model", None)


def test_input_specs_all_cells():
    """input_specs builds for every (arch x assigned shape) without error,
    and decode cells include the cache tree."""
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        for shape in configs.cells(arch):
            spec = specs_mod.input_specs(cfg, shape)
            if shape.kind == "decode":
                assert "cache" in spec
            else:
                assert spec["tokens"].shape[0] == shape.global_batch


def test_long500k_assignment_rule():
    assert any(s.name == "long_500k" for s in configs.cells("mamba2_130m"))
    assert any(s.name == "long_500k" for s in configs.cells("recurrentgemma_9b"))
    assert not any(s.name == "long_500k" for s in configs.cells("deepseek_coder_33b"))


# ---------------------------------------------------------------------------
# subprocess: 8 host devices
# ---------------------------------------------------------------------------

def _run_sub(script: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    script = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.distributed import sharding
        from repro.launch import mesh as mesh_mod
        from repro.training import optimizer as opt_mod, train_loop, data as data_mod
        from repro.models import transformer

        cfg = configs.smoke("tinyllama_1_1b")
        opt = opt_mod.AdamW(lr=1e-3)
        state = train_loop.init_train_state(jax.random.PRNGKey(0), cfg, opt)
        stream = data_mod.SyntheticLM(cfg.vocab, 16, 8, seed=0)
        batch = jax.tree.map(jnp.asarray, stream.next_batch())
        step = train_loop.make_train_step(cfg, opt)

        # single device
        s1, m1 = jax.jit(step)(state, batch)

        # 4x2 mesh sharded
        mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            p_sh = sharding.params_shardings(state.params, mesh)
            o_sh = opt_mod.AdamWState(
                step=sharding.replicated(mesh),
                mu=jax.tree.map(lambda _, s: s, state.opt_state.mu, p_sh),
                nu=jax.tree.map(lambda _, s: s, state.opt_state.nu, p_sh))
            s_sh = train_loop.TrainState(p_sh, o_sh, sharding.replicated(mesh))
            b_sh = jax.tree.map(lambda x: sharding.batch_sharding(
                mesh, x.ndim, shape=x.shape), batch)
            s2, m2 = jax.jit(step, in_shardings=(s_sh, b_sh))(state, batch)

        diff = max(abs(float(m1["loss"]) - float(m2["loss"])),
                   abs(float(m1["grad_norm"]) - float(m2["grad_norm"]))
                   / max(float(m1["grad_norm"]), 1e-9))
        pd = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))
                 for a, b in zip(jax.tree.leaves(s1.params),
                                 jax.tree.leaves(s2.params)))
        print(json.dumps({"metric_diff": diff, "param_diff": pd}))
    """)
    res = _run_sub(script)
    assert res["metric_diff"] < 5e-3
    assert res["param_diff"] < 5e-3


@pytest.mark.slow
def test_compressed_psum_bounds():
    script = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression
        from repro.launch import mesh as mesh_mod

        mesh = mesh_mod.make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)),
                        jnp.float32)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("data", None), out_specs=P("data", None))
        def f(xs):
            return compression.compressed_psum(xs[0], "data")[None]

        got = np.asarray(f(x))[0]
        want = np.asarray(jnp.sum(x, axis=0))
        scale = float(np.abs(x).max()) / 127.0
        err = float(np.abs(got - want).max())
        print(json.dumps({"err": err, "bound": 8 * scale}))
    """)
    res = _run_sub(script)
    assert res["err"] <= res["bound"] + 1e-6


@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    """The dry-run build/lower/compile path on an 8-device 4x2 mesh with a
    reduced config — the fast CI analogue of the 512-device run."""
    script = textwrap.dedent("""
        import json, dataclasses
        import jax
        from repro import configs
        from repro.core import roofline
        from repro.launch import mesh as mesh_mod
        from repro.launch import specs as specs_mod
        from repro.models.config import ShapeConfig

        cfg = dataclasses.replace(configs.smoke("qwen2_moe_a2_7b"),
                                  moe_subgroup=32)
        shape = ShapeConfig("train_tiny", "train", 32, 8)
        mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            cell = specs_mod.build_cell(cfg, shape, mesh)
            lowered = jax.jit(cell.fn,
                              in_shardings=cell.in_shardings).lower(*cell.args)
            compiled = lowered.compile()
            cost = roofline.cost_analysis_dict(compiled)
            coll = roofline.parse_collective_bytes(compiled.as_text())
        print(json.dumps({"flops": float(cost.get("flops", 0)),
                          "coll": {k: v for k, v in coll.items()}}))
    """)
    res = _run_sub(script)
    assert res["flops"] > 0
    assert sum(res["coll"].values()) > 0   # sharded step must communicate


@pytest.mark.slow
def test_decode_cell_small_mesh():
    script = textwrap.dedent("""
        import json
        import jax
        from repro import configs
        from repro.core import roofline
        from repro.launch import mesh as mesh_mod
        from repro.launch import specs as specs_mod
        from repro.models.config import ShapeConfig

        cfg = configs.smoke("tinyllama_1_1b")
        shape = ShapeConfig("decode_tiny", "decode", 64, 8)
        mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            cell = specs_mod.build_cell(cfg, shape, mesh)
            compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings) \\
                .lower(*cell.args).compile()
            cost = roofline.cost_analysis_dict(compiled)
        print(json.dumps({"flops": float(cost.get("flops", 0))}))
    """)
    res = _run_sub(script)
    assert res["flops"] > 0
