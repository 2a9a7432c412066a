"""Process start until the window opens: loading, building state,
pre-filling, compiling (or reading the compile cache) and warming up."""


def read(rec):
    return rec.setup_s
