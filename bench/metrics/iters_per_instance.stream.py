"""iters_per_instance.stream: PDHG iterations the program reports per
instance, averaged over the window's answers (a program counter)."""


def read(run):
    its = [a["iterations"] for a in run.answers]
    return sum(its) / len(its) if its else None
