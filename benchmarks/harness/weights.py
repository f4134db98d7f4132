"""Puts the benchmark's seeded weights into a Keras model, by variable
path, after checking that the model and the reference agree on what the
variables are."""


def assign(model, params: dict) -> None:
    paths = {v.path: v for v in model.variables}
    if set(paths) != set(params):
        raise ValueError(
            f"the model's variables and the reference's differ: "
            f"{sorted(set(paths) ^ set(params))[:8]}"
        )
    for path, var in paths.items():
        if tuple(var.shape) != tuple(params[path].shape):
            raise ValueError(
                f"{path}: model {var.shape}, reference {params[path].shape}"
            )
        var.assign(params[path])
