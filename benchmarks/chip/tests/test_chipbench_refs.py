"""Each plain reference ties to the program's model at a small size on
the CPU, on the benchmark's own weights."""

import jax
import jax.numpy as jnp
import numpy as np

import chipbench_tiny as tiny
import harness
import modelcfg
import optim_ref
import weights


def model_and_params(name, seed=3):
    from repro.models import build_model
    conf = tiny.tiny_config(name)
    model = build_model(modelcfg.transformer_cfg(conf))
    params = jax.jit(lambda k: weights.make(k, model.abstract_params(),
                                            conf))(weights.key_for(seed))
    return conf, model, params


def test_ssd_reference_loss_and_grads_match_the_program():
    conf, model, params = model_and_params("mamba2-1.3b")
    ref = harness.reference(conf)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (2, 33), dtype=np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    (lp, _), gp = jax.value_and_grad(model.loss, has_aux=True)(params,
                                                               batch)
    lr, gr = jax.value_and_grad(lambda p: ref.loss(p, conf, batch))(params)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale


def test_ssd_reference_control_departs():
    conf, model, params = model_and_params("mamba2-1.3b")
    ref = harness.reference(conf)
    toks = np.random.default_rng(1).integers(0, 256, (2, 33), np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    a = float(ref.loss(params, conf, batch))
    b = float(ref.loss(params, conf, batch, "fp8"))
    assert abs(a - b) > 1e-4 * abs(a)


def test_gqa_reference_logits_match_the_program():
    conf, model, params = model_and_params("qwen2-72b-L4")
    ref = harness.reference(conf)
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, 24),
                       jnp.int32)
    lp = model.logits(params, {"tokens": toks[None]})[0]
    lr = ref.logits(params, conf, toks)
    assert float(jnp.max(jnp.abs(lp - lr))) < 1e-4 * float(
        jnp.max(jnp.abs(lr)))
    lc = ref.logits(params, conf, toks, "fp8")
    assert float(jnp.max(jnp.abs(lc - lr))) > 1e-3 * float(
        jnp.max(jnp.abs(lr)))


def test_optimizer_reference_matches_the_program():
    from repro.optim import cosine_schedule, make_optimizer
    conf, model, params = model_and_params("mamba2-1.3b")
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(p.size), p.shape,
                                    p.dtype) * 1e-2, params)
    o = harness.read_json(
        f"{harness.HERE}/traffic/zero_adamw_2k.json")["optimizer"]
    opt = make_optimizer(o["name"], lr=cosine_schedule(
        o["lr"], o["warmup"], o["total"], o["min_ratio"]),
        **{k: o[k] for k in ("b1", "b2", "eps", "weight_decay",
                             "clip_norm")})
    ps, ss = params, opt.init(params)
    pr, sr = params, optim_ref.init(o, params)
    for t in (1, 2, 3):
        ps, ss, _ = opt.update(grads, ss, ps)
        pr, sr, _ = optim_ref.update(o, grads, sr, pr, float(t))
    for a, b in zip(jax.tree_util.tree_leaves(ps),
                    jax.tree_util.tree_leaves(pr)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-6
