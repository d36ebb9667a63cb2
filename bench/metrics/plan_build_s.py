"""Seconds the inspector took: ``build_advance`` up to
``block_until_ready`` on the plan (host clock)."""


def read(run):
    return run.plan_build_s
