"""The launches a traced function hands ``pallas_call``, read off its jaxpr."""
import math

import jax


def _launches(fn, *args):
    """(kernel function's name, (rows, lanes, row block)) of every
    ``pallas_call`` in fn's jaxpr."""
    launches = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                shape = eqn.invars[0].aval.shape
                mapping = eqn.params["grid_mapping"].block_mappings[0]
                name = eqn.params["jaxpr"].debug_info.func_src_info.split()[0]
                launches.append((name, (math.prod(shape[:-1]), shape[-1],
                                        mapping.block_shape[-2].block_size)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return launches


def pallas_launches(fn, *args):
    """(rows, lanes, row block) of every ``pallas_call`` in fn's jaxpr: its
    first operand as rows of lanes (a vmapped launch that is not folded
    counts each batch element's rows), and the rows a grid step takes."""
    return [launch for _, launch in _launches(fn, *args)]


def kernel_launches(fn, *args):
    """The same launches keyed by the kernel function's name
    (``_bisect_kernel``, ``_mbdf_kernel``, ...)."""
    return dict(_launches(fn, *args))
