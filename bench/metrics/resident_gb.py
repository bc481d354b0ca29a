"""Mean over the window of the process's host VmRSS plus the device's
bytes in use, sampled every 0.5 s, in GB (1e9 bytes).  Read from outside
the program: it counts what deflation really frees."""


def read(run):
    if not run.memory:
        return None
    return sum(rss + dev for _, rss, dev in run.memory) / len(run.memory) / 1e9
