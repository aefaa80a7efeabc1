"""compile_s: jax.monitoring backend-compile seconds during set-up,
persistent-cache loads included."""


def read(run):
    return run.setup_compile["compile_s"]
