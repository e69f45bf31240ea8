"""Peak HBM of the fullest chip after the window, in GB (1e9 bytes): the
live arrays (`peak_bytes_in_use`) plus what the loaded programs hold for
their temporaries (`peak_bytes_reserved`). `hbm_live_gb.train` is the
first part alone."""


def read(ctx):
    if ctx["kind"] != "train" or not sum(ctx["memory"].values()):
        return None
    return sum(ctx["memory"].values()) / 1e9
