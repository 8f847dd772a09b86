"""Operators: milliseconds a query in the interpreter between launches:
self time of the program's ``query`` and ``op:*`` spans (what is left
of the ``query`` span once ``plan``, every ``dispatch``, every
``device-sync`` and, on a miss, ``scan-stage`` are taken out), mean
over the window's untraced queries. With ``plan_ms``,
``dispatch_host_ms`` and ``device_sync_ms`` it adds up to the ``query``
span. See ``spantime.py``."""
import spantime


def read(run):
    return spantime.mean_self_ms(run, spantime.EXECUTOR)
