"""Process start to the window: imports, build_server, weights, plan, compiles, warm-up."""


def read(rec):
    return rec["setup_s"]
