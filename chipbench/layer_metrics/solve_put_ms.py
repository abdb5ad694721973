"""Device solve: median of ``put_ms`` as the program's
``ops.ell_reconverge`` spans say it: the three ``jnp.asarray`` puts of
the increase triple, host to device, between the span's preparation and
the jitted call (``dispatch_ms`` = ``put_ms`` + ``launch_ms``). Nothing
where the window ran no ELL solve, or from a program whose span does not
say."""
from chipbench import spanattr


def read(record):
    return spanattr.median(record, "ops.ell_reconverge", "put_ms")
