"""Mode ``dist``: the step-wise algorithm over ``CTOperator(mode="dist")``.

The mesh is the configuration's ``mesh``: ``data`` chips split the angles,
``model`` chips split the volume; the path ``recon --mode dist`` runs.
"""

import contextlib

from chipbench.lib.harness import Target, algorithm, annotate, log_peak


def mesh(cfg):
    """The configuration's ``(data, model)`` mesh over the local chips,
    which it has to use whole."""
    import jax
    from repro.launch.mesh import make_host_mesh
    data, model = int(cfg["mesh"]["data"]), int(cfg["mesh"]["model"])
    if data * model != jax.local_device_count():
        raise RuntimeError(f"mesh {data} x {model} for "
                           f"{jax.local_device_count()} local devices")
    return make_host_mesh(model_axis=model)


def mesh_volumes(state, shape):
    """One volume of ``state`` for each placement over more than one chip.

    ``init`` projected its start, made on one device; the iterations
    project volumes that the backprojection left on every chip of the mesh
    (CGLS's ``p``): a placement the forward operator compiles for apart."""
    import jax
    out = {}
    for a in vars(state).values():
        if isinstance(a, jax.Array) and a.shape == shape \
                and len(a.sharding.device_set) > 1:
            out.setdefault(a.sharding, a)
    return list(out.values())


def build(geo, angles, vol, cfg) -> Target:
    from repro.core.algorithms.stepwise import get_algorithm
    from repro.core.operator import CTOperator

    algo = algorithm(cfg["algorithm"])
    m = mesh(cfg)
    alg = get_algorithm(cfg["algorithm"])
    ctx = contextlib.ExitStack()
    ctx.enter_context(m)
    op = CTOperator(geo, angles, mode="dist", mesh=m,
                    bp_weight=cfg["bp_weight"], backend=cfg["backend"])
    with annotate("chipbench.setup.project"):
        b = op.A(vol)
        b.block_until_ready()
    log_peak("data projection")
    with annotate("chipbench.setup.init"):
        holder = [alg.init(b, geo, angles, op=op, **cfg.get("params", {}))]
        algo.block(holder[0])
    log_peak("init")
    with annotate("chipbench.setup.warm"):
        for v in mesh_volumes(holder[0], tuple(geo.n_voxel)):
            op.A(v).block_until_ready()
        algo.warm(alg, holder[0])
    log_peak("warm pass")

    def step():
        with annotate("chipbench.step"):
            holder[0] = alg.step(holder[0])
            algo.block(holder[0])

    def release():
        holder.clear()
        ctx.close()

    return Target(step, lambda: holder[0], b, op.kernel_config(), release,
                  list(m.devices.flat))
