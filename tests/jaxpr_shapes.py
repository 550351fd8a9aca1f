"""The shapes a traced function hands ``pallas_call``, read off its jaxpr."""
import jax


def pallas_input_shapes(fn, *args):
    """Shape of the first operand of every ``pallas_call`` in fn's jaxpr."""
    shapes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                shapes.append(tuple(eqn.invars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes
