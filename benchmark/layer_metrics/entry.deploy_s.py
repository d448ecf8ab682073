"""Seconds the host clock spends in `serve.run(...)`: worker start, seed-made
weights, engine construction. Part of `setup_s`."""


def read(ctx):
    return ctx["facts"].get("deploy_s")
