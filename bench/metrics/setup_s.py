"""Set-up time: process start to the first timed operation (imports,
device start, data, compile-cache loads or compiles, warm-up)."""


def read(run: dict) -> float | None:
    return run["setup_s"]
