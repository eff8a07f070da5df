"""Mean requests per batched cloud launch of the window."""


def read(run):
    groups = getattr(run.system, "window_groups", [])
    if not groups:
        return None
    return sum(len(g.uids) for g in groups) / len(groups)
