"""Mesh-trainer integration: the shard_map collective train step agrees with
the single-device global-view simulation (same masks, same init, same data),
run in a subprocess with 8 forced host devices (4 data × 2 model)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=570)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_mesh_train_step_matches_global_simulation():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core import rps as rps_lib
        from repro.launch import sharding as shlib
        from repro.models import build_model
        from repro.models.inputs import make_batch
        from repro.optim import make_optimizer
        from repro.train.trainer import TrainConfig, make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                                  n_layers=2, shard_acts=True)
        model = build_model(cfg, grouped=True)
        tcfg = TrainConfig(optimizer="sgd", lr=0.1, drop_rate=0.3,
                           aggregator="rps_model", microbatch=1)
        init_state, train_step, state_shardings = make_train_setup(
            model, cfg, tcfg, mesh, rps_axes=("data",))
        params, opt_state = init_state(jax.random.PRNGKey(0))
        n = 4
        batch = jax.tree.map(
            lambda x: x.reshape((n, -1) + x.shape[1:]),
            make_batch(cfg, 8, 32))
        key = jax.random.PRNGKey(42)

        with jax.set_mesh(mesh):
            p_sh, _ = state_shardings(jax.eval_shape(lambda t: t, params))
            step = jax.jit(train_step)
            new_params, opt_state, metrics = step(params, opt_state, batch,
                                                  jnp.int32(0), key)
        loss_mesh = float(metrics["loss"])

        # global-view replica: vmapped grads + SGD + global exchange
        # (inside set_mesh: the model's sharding constraints need a context)
        def total(ps, bs):
            return jnp.sum(jax.vmap(lambda p, b: model.loss(p, b)[0])(ps, bs))
        with jax.set_mesh(mesh):
            loss_g, grads = jax.jit(jax.value_and_grad(total))(params, batch)
            opt = make_optimizer("sgd")
            stepped, _ = opt.update(grads, opt.init(params), params,
                                    jnp.float32(0.1))
            expect = rps_lib.rps_exchange_global(stepped, key, 0.3, n,
                                                 mode="model")
        err = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            new_params, expect)))
        assert abs(loss_mesh - float(loss_g) / n) < 1e-3, (loss_mesh, loss_g)
        assert err < 5e-3, f"param mismatch {err}"
        print("TRAINER_OK", loss_mesh, err)
    """) % SRC
    out = _run(code)
    assert "TRAINER_OK" in out, out


def test_mesh_train_loss_decreases():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.inputs import make_batch
        from repro.train.trainer import TrainConfig, make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = dataclasses.replace(get_config("gemma3-1b").reduced(),
                                  n_layers=2, shard_acts=True)
        model = build_model(cfg, grouped=True)
        tcfg = TrainConfig(optimizer="sgd", lr=0.3, drop_rate=0.1,
                           aggregator="rps_model", microbatch=2)
        init_state, train_step, _ = make_train_setup(
            model, cfg, tcfg, mesh, rps_axes=("data",))
        params, opt_state = init_state(jax.random.PRNGKey(0))
        with jax.set_mesh(mesh):
            step = jax.jit(train_step)
            losses = []
            batch = jax.tree.map(
                lambda x: x.reshape((4, -1) + x.shape[1:]),
                make_batch(cfg, 8, 32, seed=0))
            for t in range(8):   # fixed batch: memorisation must reduce loss
                params, opt_state, m = step(params, opt_state, batch,
                                            jnp.int32(t),
                                            jax.random.PRNGKey(t))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        print("DECREASE_OK", losses[0], losses[-1])
    """) % SRC
    out = _run(code)
    assert "DECREASE_OK" in out, out


def test_mesh_train_step_with_channel_matches_global():
    """With a Gilbert–Elliott channel configured, the mesh step consumes the
    channel's masks and carries its state: one step must equal the global
    exchange evaluated with the same (rs, ag) pair."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.core import rps as rps_lib
        from repro.models import build_model
        from repro.models.inputs import make_batch
        from repro.optim import make_optimizer
        from repro.train.trainer import TrainConfig, make_train_setup

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = dataclasses.replace(get_config("deepseek-7b").reduced(),
                                  n_layers=2, shard_acts=True)
        model = build_model(cfg, grouped=True)
        tcfg = TrainConfig(optimizer="sgd", lr=0.1, aggregator="rps_model",
                           channel="ge:p_bad=1.0,burst=4,p=0.3")
        init_state, train_step, _ = make_train_setup(
            model, cfg, tcfg, mesh, rps_axes=("data",))
        params, opt_state = init_state(jax.random.PRNGKey(0))
        ch_state = train_step.init_channel_state(jax.random.PRNGKey(1))
        n = 4
        batch = jax.tree.map(
            lambda x: x.reshape((n, -1) + x.shape[1:]),
            make_batch(cfg, 8, 32))
        key = jax.random.PRNGKey(42)

        with jax.set_mesh(mesh):
            step = jax.jit(train_step)
            new_params, opt_state, metrics, ch_state2 = step(
                params, opt_state, batch, jnp.int32(0), key, ch_state)

        # the channel state must actually evolve (GE link states flip)
        assert not np.array_equal(np.asarray(ch_state["bad"]),
                                  np.asarray(ch_state2["bad"]))

        def total(ps, bs):
            return jnp.sum(jax.vmap(lambda p, b: model.loss(p, b)[0])(ps, bs))
        with jax.set_mesh(mesh):
            loss_g, grads = jax.jit(jax.value_and_grad(total))(params, batch)
            opt = make_optimizer("sgd")
            stepped, _ = opt.update(grads, opt.init(params), params,
                                    jnp.float32(0.1))
            rs, ag, _ = train_step.channel.sample(key, ch_state)
            expect = rps_lib.rps_exchange_global(
                stepped, key, 0.0, n, mode="model", masks=(rs, ag))
        err = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            new_params, expect)))
        assert err < 5e-3, f"param mismatch {err}"
        print("CHANNEL_TRAINER_OK", err)
    """) % SRC
    out = _run(code)
    assert "CHANNEL_TRAINER_OK" in out, out
