"""Entry ``qn_train``: quasi-Newton training by Algorithm 1 through
``repro.train.QNTrainer.step_fn``, steps back to back.

Set-up builds the trainer and its compiled step, makes the weights from
``--seed`` on the device in one jitted call, and drives that same step
through its first ``setup_steps`` steps, on batches made on the device
from the seed (every row differs). The window continues from there
until ``--seconds`` have passed and ends with the last step that
started inside it. ``tokens_per_s`` is the tokens of the window's steps
over the window's time.

``correct``: once the window has closed and the program's state is
freed, the plain reference (``bench/reference/qn_tree.py`` over
``bench/reference/xlstm.py``) repeats the first three steps from the
same weights and batches. Compared: each step's loss, and per leaf the
norm of the parameters' change after step 1 (R1's local step, so the
first gradient as the optimizer took it, times ``local_lr``) and after
step 3, each against the reference's, by the worst leaf
(:func:`leaf_gap`).
"""
from __future__ import annotations

import gc
import time

import numpy as np

#: a leaf whose reference gradient norm (g_cq of step 1) is under this
#: share of the median leaf's moves by round-off alone: left out
GRAD_FLOOR = 1e-3


def leaf_gap(got: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """Worst leaf of |‖Δ_got‖ − ‖Δ_ref‖| over the larger of the leaf's
    reference norm and the median leaf's."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    base = np.maximum(ref, np.median(ref[keep]))
    return float((np.abs(got - ref) / base)[keep].max())


def model_config(cf: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cf["name"], family=cf["family"], n_layers=cf["n_layers"],
        d_model=cf["d_model"], n_heads=cf["n_heads"],
        n_kv_heads=cf["n_kv_heads"], d_ff=cf["d_ff"], vocab=cf["vocab"],
        slstm_at=tuple(cf["slstm_at"]), norm_eps=cf["norm_eps"],
        dtype=cf["dtype"])


def trainer(r):
    from repro.configs.base import TreeProtocolConfig
    from repro.models.model import Model
    from repro.train.trainer import QNTrainConfig, QNTrainer

    cf, tr = r.config, r.traffic
    proto = TreeProtocolConfig(
        hist=cf["hist"], lr=cf["lr"], local_lr=cf["local_lr"],
        local_steps=cf["local_steps"], eps=tr["eps"],
        aggregator=cf["aggregator"], K=cf["K"])
    qcfg = QNTrainConfig(n_machines=tr["machines"], protocol=proto,
                         attack=tr["attack"])
    return QNTrainer(Model(model_config(cf), remat=True), qcfg)


def init_weights(key, shapes, cf: dict):
    """The weights, made from ``key`` on the device in one jitted call,
    shaped like ``shapes`` (the program's parameter tree): normal with
    scale 1/sqrt(fan-in) for matrices, 0.02 for the embedding, head and
    gate projections, 0.1 for the recurrent weights; norms 1; the mLSTM
    forget-gate bias linspace(3, 6), the sLSTM bias 1 on its third
    quarter; everything else 0."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    H, d = cf["n_heads"], cf["d_model"]

    def make(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
            k = jax.random.fold_in(key, i)
            if name in ("norm", "norm_f"):
                x = jnp.ones(sd.shape)
            elif name == "b_if":
                x = jnp.concatenate([jnp.zeros((H,)),
                                     jnp.linspace(3.0, 6.0, H)])
            elif name == "b":
                x = jnp.concatenate([jnp.zeros((2 * d,)), jnp.ones((d,)),
                                     jnp.zeros((d,))])
            else:
                scale = {"embed": 0.02, "lm_head": 0.02, "w_if": 0.02,
                         "r_h": 0.1}.get(name, 1.0 / np.sqrt(sd.shape[0]))
                x = scale * jax.random.normal(k, sd.shape)
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)
    return jax.jit(make)(key)


def batch_maker(r):
    """``step -> {"tokens", "labels"}``, (batch, seq) int32 each, from
    the seed; labels are the next tokens of one uniform stream."""
    import jax
    tr, vocab = r.traffic, r.config["vocab"]
    root = r.key("batches")

    @jax.jit
    def make(i):
        toks = jax.random.randint(jax.random.fold_in(root, i),
                                  (tr["batch"], tr["seq"] + 1), 0, vocab)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return make


def leaf_norms(a, b):
    """Per-leaf ‖a − b‖ in float32, as a host array in leaf order."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return jnp.stack([jnp.linalg.norm((x.astype(jnp.float32)
                                           - y.astype(jnp.float32)).ravel())
                          for x, y in zip(jax.tree_util.tree_leaves(a),
                                          jax.tree_util.tree_leaves(b))])
    return np.asarray(norms(a, b))


def program_steps(r, fault=None, tn=None):
    """Build the compiled step (or take ``tn``'s) and drive it through
    the set-up steps. Returns the live state and what the comparison
    needs."""
    import jax
    import jax.numpy as jnp

    tr = r.traffic
    with r.span("bench.setup.build"):
        tn = trainer(r) if tn is None else tn
        shapes = jax.eval_shape(tn.model.init, jax.random.PRNGKey(0))
        theta0 = init_weights(r.key("params"), shapes, r.config)
        mem = tn.init_memory(theta0)
        batch = batch_maker(r)
    byz = jnp.arange(tr["machines"]) < tr["byzantine"]
    pkey = r.key("protocol")
    step = tn.step_fn
    if fault is not None:
        step = planted(step, fault, tr)

    def one(theta, mem, i):
        theta, mem, met = step(theta, mem, batch(i),
                               jax.random.fold_in(pkey, i), byz)
        return theta, mem, float(met["loss"])

    losses, theta = [], theta0
    for i in range(tr["setup_steps"]):
        with r.span("bench.setup.step"):
            theta, mem, loss = one(theta, mem, i)
        losses.append(loss)
        if i == 0:
            d1 = leaf_norms(theta, theta0)
    d3 = leaf_norms(theta, theta0)
    del theta0
    return tn, one, theta, mem, {"losses": losses, "d1": d1, "d3": d3}


def planted(step, fault: str, tr: dict):
    """The step with one fault the comparison has to catch."""

    def wrapped(theta, mem, batch, key, byz):
        if fault == "half_batch":   # half of each machine's rows left out
            m, b = tr["machines"], tr["batch"]
            keep = np.arange(b).reshape(m, -1)[:, : b // m // 2].ravel()
            batch = {k: v[keep] for k, v in batch.items()}
        new, mem2, met = step(theta, mem, batch, key, byz)
        if fault == "state_unchanged":
            return theta, mem, met
        if fault == "answer":       # one leaf's update doubled
            new = dict(new)
            new["lm_head"] = theta["lm_head"] + 2 * (new["lm_head"]
                                                     - theta["lm_head"])
        return new, mem2, met
    return wrapped


def reference_steps(r, n_steps: int, variant: str = "reference"):
    """The plain reference's first ``n_steps`` steps from the same
    weights and batches: losses, per-leaf change norms after step 1 and
    after the last, and step 1's aggregated gradient norms per leaf.
    ``variant``: ``"reference"``, ``"control"`` (the values stored one
    precision lower: float8 e4m3 for bfloat16 leaves, bfloat16 for
    float32 ones), or a fault planted in the reference in the program's
    place (``"half_batch"``, ``"answer"``)."""
    import jax
    import jax.numpy as jnp
    from bench.reference import qn_tree, xlstm

    cf, tr = r.config, r.traffic
    # significant bits of the precision below each storage dtype: float8
    # e4m3 (4) below bfloat16, bfloat16 (8) below float32
    lower_bits = {jnp.dtype(jnp.bfloat16): 4, jnp.dtype(jnp.float32): 8}

    def store(x, like):
        if variant == "control":
            x = round_significand(x.astype(jnp.float32),
                                  lower_bits[jnp.dtype(like.dtype)])
        return x.astype(like.dtype)

    shapes = jax.eval_shape(trainer(r).model.init, jax.random.PRNGKey(0))
    theta0 = init_weights(r.key("params"), shapes, cf)
    if variant == "control":
        theta0 = jax.jit(lambda t: qn_tree.tmap(lambda x: store(x, x), t))(
            theta0)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, lab: xlstm.loss(p, t, lab, cf)))
    step = qn_tree.make_step(grad_fn, tr["machines"], tr["byzantine"], cf,
                             store)
    batch = batch_maker(r)
    mems = [qn_tree.Memory(theta0, cf["hist"]) for _ in range(tr["machines"])]
    theta, losses = theta0, []
    for i in range(n_steps):
        b = batch(i)
        tok, lab = b["tokens"], b["labels"]
        if variant == "half_batch":
            m, bs = tr["machines"], tr["batch"]
            keep = np.arange(bs).reshape(m, -1)[:, : bs // m // 2].ravel()
            tok, lab = tok[keep], lab[keep]
        new, loss, g_cq = step(theta, mems, tok, lab)
        if variant == "answer":
            new = dict(new)
            new["lm_head"] = theta["lm_head"] + 2 * (new["lm_head"]
                                                     - theta["lm_head"])
        theta = new
        losses.append(loss)
        if i == 0:
            d1 = leaf_norms(theta, theta0)
            g1 = leaf_norms(g_cq, qn_tree.tmap(jnp.zeros_like, g_cq))
    return {"losses": losses, "d1": d1, "d3": leaf_norms(theta, theta0),
            "g1": g1}


def round_significand(x, bits: int):
    """``x`` rounded to ``bits`` significant bits, to nearest (written
    out in arithmetic, so that no compiler folds a pair of casts)."""
    import jax.numpy as jnp
    m, e = jnp.frexp(x)
    scale = 2.0 ** bits
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def readings(prog: dict, ref: dict, names=None) -> dict:
    keep = ref["g1"] >= GRAD_FLOOR * np.median(ref["g1"])
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "grad1_gap": leaf_gap(prog["d1"], ref["d1"], keep),
           "delta3_gap": leaf_gap(prog["d3"], ref["d3"], keep),
           "leaves_compared": int(keep.sum()),
           "leaves": int(keep.size)}
    if names is not None:       # the leaves that read most, for a look
        base = np.maximum(ref["d1"], np.median(ref["d1"][keep]))
        gap = np.where(keep, np.abs(prog["d1"] - ref["d1"]) / base, 0)
        out["worst"] = [[names[i], float(prog["d1"][i]),
                         float(ref["d1"][i]), float(ref["g1"][i]),
                         float(prog["d3"][i]), float(ref["d3"][i])]
                        for i in np.argsort(-gap)[:12]]
        out["median_d1"] = float(np.median(ref["d1"][keep]))
    return out


def free() -> None:
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def run(r, fault=None):
    import jax
    from bench import counts

    cf, tr = r.config, r.traffic
    tn, one, theta, mem, prog = program_steps(r, fault)
    step_flops = counts.qn_step_flops(cf, tr["batch"], tr["seq"])
    leaves = [(tuple(x.shape), x.dtype.itemsize)
              for x in jax.tree_util.tree_leaves(theta)]
    step_bytes = counts.tree_aggregation_bytes(leaves, tr["machines"], 5)

    n, i, failed = 0, tr["setup_steps"], 0
    with r.window():
        t_end = time.perf_counter() + r.seconds
        while True:
            with r.span("bench.step"):
                theta, mem, loss = one(theta, mem, i)
            failed += not np.isfinite(loss)
            n, i = n + 1, i + 1
            if time.perf_counter() >= t_end:
                break
    r.read_memory_peak()
    counts_w = r.in_window()
    r.values.update(steps=n, window_model_flops=n * step_flops,
                    ostat_bytes=n * step_bytes,
                    compiles_in_window=counts_w["compiles"]
                    - counts_w["cache_hits"])
    r.log(f"window: {n} steps, {counts_w['traces']} traces, "
          f"{counts_w['compiles'] - counts_w['cache_hits']} compiles; "
          f"set-up losses {prog['losses']}")
    del tn, one, theta, mem
    free()
    with r.span("bench.reference"):
        ref = reference_steps(r, len(prog["losses"]))
    rd = readings(prog, ref)
    r.log(f"reference losses {ref['losses']}; readings {rd}")
    for name in ("loss_gap", "grad1_gap", "delta3_gap"):
        r.check(name, rd[name], tr["limits"][name])
    return {"correct": failed == 0, "attempted": n, "failed": failed,
            "metrics": {"tokens_per_s":
                        n * tr["batch"] * tr["seq"] / r.window_s}}


def calibrate(r, variant: str, cache: dict) -> dict:
    """Readings of one seed with no window: ``"program"``, ``"control"``
    (see :func:`reference_steps`) or a fault planted in the reference
    (``"half_batch"``, ``"answer"``). ``cache`` keeps the compiled step
    from one seed to the next."""
    n = r.traffic["setup_steps"]
    if variant == "program":
        tn, one, theta, mem, prog = program_steps(r, tn=cache.get("trainer"))
        cache["trainer"] = tn
        del one, theta, mem
        gc.collect()
    else:
        prog = reference_steps(r, n, variant)
        free()
    ref = reference_steps(r, n)
    free()
    import jax
    shapes = jax.eval_shape(trainer(r).model.init, jax.random.PRNGKey(0))
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    return readings(prog, ref, names)
