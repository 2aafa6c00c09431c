"""images_per_s: images of the requests answered inside the window, over
the window's length (host clock).  Closed loop at saturation: what a user
paying for chip time gets."""


def read(run):
    return run.images_in_window / run.seconds
