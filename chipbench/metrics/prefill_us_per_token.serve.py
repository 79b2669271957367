"""Host time of the window's untraced prefills, behind their device sync,
over the prompt tokens they took in."""


def read(*, summary, **_):
    prefills = summary.get("prefills") or []
    tokens = sum(n for _, n in prefills)
    return sum(t for t, _ in prefills) / tokens * 1e6 if tokens else None
