"""How evenly the unordered elections spread: over the ``$share`` groups
the stand-in was handed at least 100 deliveries for inside the window, the
largest member's deliveries over the group's mean a seeded member, the
worst group's (``run.share_skew``; 1.0 is even). From the stand-in's own
record of who was elected, not from the program's balancer. ``$oshare``
promises no balance (a rendezvous hash a topic): its maximum is in the
log, not here."""


def read(ctx):
    skew = (ctx.get("share_skew") or {}).get("$share")
    if not skew or not skew["groups"]:
        return None
    return skew["max"]
