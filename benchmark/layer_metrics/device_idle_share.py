"""Share of the traced window in which no operation ran on the device.
Window: the host's, from the last warm-up chunk's completion to the last
traced chunk's, so every chunk counts with the gap before it.  The busy time
is the trace's and the window the host's: a negative share means that the
two disagree, and is printed as it reads."""


def read(facts):
    t = facts["trace"]
    if not t or not facts["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / facts["window_s"])
