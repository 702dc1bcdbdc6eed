"""One phase or counter of set-up, as the harness or the program recorded it."""


def read(run, args):
    return run["setup"].get(args["key"])
