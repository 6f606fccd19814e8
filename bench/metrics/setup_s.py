"""setup_s: seconds from process start to the first timed call: JAX's
start-up, generating the instances, converting them to the program's
input, and the warm-up call with its compiles or cache loads."""


def read(run):
    return run.setup_s
