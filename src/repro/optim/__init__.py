"""Pure-JAX pytree optimizers (no external deps)."""

from repro.optim.optimizers import (  # noqa: F401
    adam, adamw, sgd, rowwise_adagrad, apply_updates, linear_decay,
    OptState, Optimizer, RowWiseAdagrad,
)
