"""Host seconds to trace and plan the configuration (ProfileStore.plan)
in set-up."""


def read(run):
    return getattr(run, "plan_s", None) or None
