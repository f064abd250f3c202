"""Multi-device semantics tests.

The main test process sees one CPU device (smoke tests must not inherit a
forced device count), so anything that needs real multi-device SPMD runs in
a subprocess with ``--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        assert jax.device_count() == {n}
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


@pytest.mark.slow
def test_vp_take_8way():
    run_with_devices("""
        from repro.runtime.sharding import make_vp_take
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        take = make_vp_take(mesh, leading=("data",))
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
        table = jax.device_put(table, NamedSharding(mesh, P("model", None)))
        ids = jnp.asarray(rng.integers(0, 64, (8, 5)), jnp.int32)
        ids = jax.device_put(ids, NamedSharding(mesh, P(("data",), None)))
        got = jax.jit(take)(table, ids)
        want = jnp.take(jax.device_get(table), jax.device_get(ids), axis=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
        print("vp_take ok")
    """)


@pytest.mark.slow
def test_compressed_grad_allreduce_8way():
    run_with_devices("""
        from repro.optim import compression
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        fn = compression.make_compressed_grad_allreduce(mesh, axis="data")
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)}
        e = compression.init_error_state(g)
        mean, new_e = jax.jit(fn)(g, e)
        # replicated identical grads: mean == dequant(quant(g)), error small
        err = np.abs(np.asarray(mean["w"]) - np.asarray(g["w"])).max()
        scale = np.abs(np.asarray(g["w"])).max() / 127.0
        assert err <= scale * 0.51 + 1e-6, (err, scale)
        print("compressed allreduce ok", err)
    """)


@pytest.mark.slow
def test_smoke_train_step_sharded_8way():
    """A reduced LM train step under a (2,4) data x model mesh: the full
    production sharding rules, 8-way."""
    run_with_devices("""
        import repro.configs as C
        from repro.optim import adamw
        spec = C.get("glm4-9b")
        cfg = C.cell_model_cfg(spec, "train_4k", smoke=True)
        import dataclasses
        cfg = dataclasses.replace(cfg, n_head=4, n_kv=2, d_model=64)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        params = C.init_params(spec, cfg, jax.random.PRNGKey(0))
        p_specs = C.param_specs(spec, params, mesh)
        named = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                             is_leaf=lambda x: isinstance(x, P))
        params = jax.tree.map(jax.device_put, params, named)
        opt = adamw.init_state(params)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 32), jnp.int32)}
        step = jax.jit(C.make_train_step(spec, cfg))
        p2, o2, m = step(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        print("sharded train step ok", float(m["loss"]))
    """)


@pytest.mark.slow
def test_batched_tccs_queries_shardable():
    """The batched TCCS engine's (B, N) propagation shards over queries."""
    run_with_devices("""
        from repro.core.temporal_graph import gen_temporal_graph
        from repro.core.pecb_index import build_pecb_index
        from repro.core.batch_query import to_device, batch_query
        g = gen_temporal_graph(n=40, m=250, t_max=15, seed=1)
        idx = build_pecb_index(g, 2)
        dix = to_device(idx)
        rng = np.random.default_rng(0)
        B = 64
        u = jnp.asarray(rng.integers(0, g.n, B), jnp.int32)
        ts = jnp.asarray(rng.integers(1, g.t_max + 1, B), jnp.int32)
        te = jnp.minimum(ts + 5, g.t_max)
        mesh = jax.make_mesh((8,), ("q",), axis_types=(AxisType.Auto,))
        sh = NamedSharding(mesh, P("q"))
        out, _ = batch_query(dix, jax.device_put(u, sh),
                             jax.device_put(ts, sh), jax.device_put(te, sh))
        # spot-check against the host index
        mask = np.asarray(out)
        for i in range(0, B, 7):
            want = idx._component_vertices(int(u[i]), int(ts[i]), int(te[i]))
            got = set(np.nonzero(mask[i])[0].tolist())
            assert got == want
        print("sharded batch query ok")
    """)


@pytest.mark.slow
def test_a2a_moe_matches_reference_dispatch():
    """The shard_map all-to-all MoE (runtime/moe_a2a.py) is bit-equal to the
    single-device reference dispatch when capacity is non-binding."""
    run_with_devices("""
        from repro.models import transformer as tfm
        from repro.runtime.moe_a2a import make_a2a_moe
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        mcfg = tfm.MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                             capacity_factor=8.0)
        cfg = tfm.LMConfig("t", n_layer=1, d_model=64, n_head=2, n_kv=2,
                           d_ff=0, vocab=64, d_head=16, moe=mcfg,
                           dtype=jnp.float32, remat=False)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))
        ref_out, _ = tfm.moe_ffn(lp, cfg, x)
        a2a = make_a2a_moe(mesh, ("data",))
        xs = jax.device_put(x, NamedSharding(mesh, P(("data",), None, None)))
        lps = {k: jax.device_put(v, NamedSharding(
                   mesh, P("model", None, None) if k in ("wi", "wg", "wo") else P()))
               for k, v in lp.items()}
        out, aux = jax.jit(lambda p, xx: a2a(p, cfg, xx))(lps, xs)
        err = float(jnp.abs(out - ref_out).max())
        assert err < 1e-4, err
        # gradients flow through the a2a exchanges
        g = jax.grad(lambda p: jnp.sum(a2a(p, cfg, xs)[0] ** 2))(lps)
        assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(g))
        print("a2a moe ok", err)
    """)
