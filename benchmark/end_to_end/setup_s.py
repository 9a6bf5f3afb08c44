"""Process start -> window start: members, JAX, data, connects,
compilation or cache hits, warm-up traffic."""

def value(run) -> float:
    return run.setup_s
