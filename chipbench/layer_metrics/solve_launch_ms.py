"""Device solve: median of ``launch_ms`` as the program's
``ops.ell_reconverge`` spans say it: the call of the jitted
``_ell_reconverge`` itself, from the last put to its return with the
outputs as futures (jax's argument handling and the enqueue; the device
runs on behind it, which ``solve_wait_ms`` then waits for). Nothing
where the window ran no ELL solve, or from a program whose span does not
say."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "ops.ell_reconverge", "launch_ms")
