"""Kernel selection and kernel tables: `Trainer.setup_s` `tuner` (absent
when `tuning.json` was loaded) plus `tables` (built or loaded)."""


def read(ctx):
    setup = ctx["setup"]
    if "tables" not in setup and "tuner" not in setup:
        return None
    return setup.get("tuner", 0.0) + setup.get("tables", 0.0)
