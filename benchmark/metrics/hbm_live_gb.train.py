"""The live arrays' part of the HBM peak (`peak_bytes_in_use`: weights,
optimizer state, feeds), in GB. The rest of `peak_hbm_gb.train` is the
step program's temporaries, its activations and workspace."""


def read(ctx):
    if ctx["kind"] != "train" or not ctx["memory"]["live"]:
        return None
    return ctx["memory"]["live"] / 1e9
