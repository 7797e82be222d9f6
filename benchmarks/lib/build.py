"""A configuration file -> the program's model object.  The file names the
builder as a dotted path with its keyword arguments, so a new family is a new
file and no branch here."""

from benchmarks.lib.cells import resolve


def model_from(config, extra_kwargs=None):
    spec = config["model"]
    kwargs = dict(spec["kwargs"], **(extra_kwargs or {}))
    cfg = resolve(spec["config"])(**kwargs)
    return resolve(spec["module"])(cfg)


def jax_seed(seed):
    """``--seed`` may pass 2**31; numpy takes it whole, a JAX key takes it
    folded."""
    return int(seed) % (2 ** 31 - 1)
