"""Mean number of downloaded blocks waiting in the replay queue, sampled
through the window.  Near 0 means the sources, not the node, set the pace."""


def read(window, params):
    if not window.buffered:
        return None
    return sum(window.buffered) / len(window.buffered)
