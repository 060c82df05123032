"""Images of every request sent in the window, over the seconds from
the window's start until the last of them came back (images/s).

Nothing is sent once the window's time is up, and what is in flight
then is waited for: all of that work counts, over all of that time, so
the rate does not jump by a batch with where the window's edge falls.
"""


def read(win):
    return sum(s.images for s in win.sent
               if s.status == "served") / (win.t_end - win.t0)
