"""Production mesh definitions and logical->physical spec translation.

Logical axes used throughout the model code: "data" (batch / FSDP) and
"model" (TP / EP).  The multi-pod mesh adds a leading "pod" axis which is
folded into data parallelism: every logical "data" entry becomes
("pod", "data").
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) == need:
        return jax.make_mesh(shape, axes)
    # dry-run host platform exposes 512 devices; single-pod uses the first 256
    return jax.make_mesh(shape, axes, devices=devs[:need])


def make_test_mesh() -> Mesh:
    """Single-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_local_mesh(data: int, model: int) -> Mesh:
    """(data, model) mesh over the first ``data * model`` local devices."""
    need = data * model
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(f"a {data}x{model} mesh needs {need} devices; "
                         f"{len(devs)} present")
    return jax.make_mesh((data, model), ("data", "model"), devices=devs[:need])


def translate_spec(spec: P, *, multi_pod: bool) -> P:
    """Map logical 'data' entries to ('pod', 'data') on the multi-pod mesh."""
    if not multi_pod:
        return spec
    out = []
    for entry in spec:
        if entry == "data":
            out.append(("pod", "data"))
        elif isinstance(entry, (tuple, list)) and "data" in entry:
            expanded = []
            for e in entry:
                if e == "data":
                    expanded.extend(["pod", "data"])
                else:
                    expanded.append(e)
            out.append(tuple(expanded))
        else:
            out.append(entry)
    return P(*out)


def shardings_for(mesh: Mesh, spec_tree, *, multi_pod: bool):
    """Spec pytree -> NamedSharding pytree on the given mesh."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, translate_spec(s, multi_pod=multi_pod)),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims the mesh cannot divide evenly.

    For tuple entries (e.g. ("pod", "data")) the longest prefix whose
    product divides the dim is kept.  Configs with awkward sizes (a vocab
    of 256206, 8 experts on a 16-wide model axis, batch=1 decode) then
    lower cleanly with those dims replicated instead of erroring out.
    """
    sizes = _axis_sizes(mesh)
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = []
        prod = 1
        for a in axes:
            if shape[i] % (prod * sizes[a]) == 0:
                kept.append(a)
                prod *= sizes[a]
            else:
                break
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def shardings_for_structs(mesh: Mesh, spec_tree, struct_tree, *,
                          multi_pod: bool):
    """Like ``shardings_for`` but validated against concrete array shapes."""
    specs = jax.tree.map(
        lambda s: translate_spec(s, multi_pod=multi_pod),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.tree.map(
        lambda s, a: NamedSharding(mesh, sanitize_spec(s, a.shape, mesh)),
        specs, struct_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def dp_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def model_size(mesh: Mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("model", 1)


def batch_spec(batch: int, mesh: Mesh) -> P:
    """Shard batch over data(+pod) when divisible, else replicate."""
    if batch % dp_size(mesh) == 0:
        if "pod" in mesh.axis_names:
            return P(("pod", "data"))
        return P("data")
    return P(None)
