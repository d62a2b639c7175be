from . import pytree  # noqa: F401
