"""Images whose results came inside the window, over the window's length
(``bench/drive.py`` defines the window of each kind of loop)."""


def read(run):
    return run.img_per_s
