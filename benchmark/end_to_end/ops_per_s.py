"""Operations acknowledged inside the window, over the whole fleet,
per second of the window as it ran."""

def value(run) -> float:
    return run.result['acked'] / run.window_s
