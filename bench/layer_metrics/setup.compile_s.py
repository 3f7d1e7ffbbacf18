"""Backend compile seconds during set-up, persistent-cache loads included (compile_cache.stats)."""


def read(rec):
    return rec["setup_compile_s"]
