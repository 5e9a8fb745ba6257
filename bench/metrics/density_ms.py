"""Device time of the operations under the program's ``scf.density``
scope per SCF iteration, mean over the devices, in ms."""
from bench import scopes


def read(tr, info):
    return scopes.per_step_ms(tr, info, "scf.density")
