"""Shared by the structural pins (tests/test_mesh_cell.py,
tests/test_commit_dedup.py): a traced program's equations with the name
stack each stands under."""

import jax


def scoped_eqns(jaxpr, stack=""):
    """(name stack, equation) of every equation of a traced program,
    the bodies of its loops, maps and calls included."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield here, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from scoped_eqns(sub, here)
