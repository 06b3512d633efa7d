"""Window delta of a number in /stats (a path of keys)."""


def read(ctx, args):
    def at(edge):
        v = edge["stats"]
        for k in args["path"]:
            v = v[k]
        return float(v)

    return at(ctx["edge1"]) - at(ctx["edge0"])
