"""Process start to the opening of the window, by the harness's own clock."""


def read(src):
    return src["setup_s"]
