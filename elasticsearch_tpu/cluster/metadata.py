"""Cluster metadata: aliases, index templates, component templates.

The reference keeps these in the cluster state (reference:
cluster/metadata/Metadata.java — `aliases` live inside IndexMetadata with an
AliasMetadata entry per alias, cluster/metadata/AliasMetadata.java;
composable templates in cluster/metadata/ComposableIndexTemplate.java +
ComponentTemplate.java, applied at index-creation time by
MetadataCreateIndexService / MetadataIndexTemplateService.java
`resolveSettings`/`resolveMappings` which compose `composed_of` component
templates in order, then the template's own overlay, then the request).
Index-name expression resolution (wildcards, `-` exclusions, `_all`,
aliases) mirrors IndexNameExpressionResolver.java.

Here the store is a small host-side JSON-persisted registry owned by the
node engine; the distributed-state variant rides the coordinator's cluster
state (cluster/state.py) unchanged — this module is pure data + resolution
logic with no IO beyond load/save.
"""

from __future__ import annotations

import fnmatch
import json
import os
import threading

from ..utils.errors import (
    IllegalArgumentError,
    IndexNotFoundError,
    ResourceNotFoundError,
)


def deep_merge(base: dict, overlay: dict) -> dict:
    """Recursive dict merge, overlay wins; the composition rule for template
    settings/mappings (reference behavior: MetadataIndexTemplateService
    resolveSettings — later templates override earlier, XContentHelper
    mergeDefaults for mappings)."""
    out = dict(base)
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = v
    return out


class MetadataStore:
    """aliases: {alias_name: {index_name: {filter?, is_write_index?,
    routing?}}}; index_templates / component_templates: {name: body}."""

    def __init__(self, data_path: str | None = None):
        self.data_path = data_path
        self.aliases: dict[str, dict[str, dict]] = {}
        self.index_templates: dict[str, dict] = {}
        self.component_templates: dict[str, dict] = {}
        self.stored_scripts: dict[str, dict] = {}
        self.data_streams: dict[str, dict] = {}
        self.ilm_policies: dict[str, dict] = {}
        self.persistent_tasks: dict[str, dict] = {}
        self.security: dict = {"users": {}, "roles": {}, "api_keys": {}}
        self.transforms: dict[str, dict] = {}
        # free-form persisted buckets for feature modules (slm/watcher/
        # enrich/ccr/...): {bucket_name: {key: json-able value}}
        self.extras: dict[str, dict] = {}
        self._load()

    # ---- persistence -----------------------------------------------------

    def _file(self):
        return os.path.join(self.data_path, "metadata.json") if self.data_path else None

    def _load(self):
        f = self._file()
        if f and os.path.exists(f):
            with open(f, encoding="utf-8") as fh:
                state = json.load(fh)
            self.aliases = state.get("aliases", {})
            self.index_templates = state.get("index_templates", {})
            self.component_templates = state.get("component_templates", {})
            self.stored_scripts = state.get("stored_scripts", {})
            self.data_streams = state.get("data_streams", {})
            self.ilm_policies = state.get("ilm_policies", {})
            self.persistent_tasks = state.get("persistent_tasks", {})
            self.security = state.get(
                "security", {"users": {}, "roles": {}, "api_keys": {}})
            self.transforms = state.get("transforms", {})
            self.extras = state.get("extras", {})

    def save(self):
        f = self._file()
        if not f:
            return
        # the saver's own temp file: two threads sharing one would find it
        # gone at the later `os.replace` (the watcher's executor saves too)
        tmp = f"{f}.{threading.get_ident()}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(
                {
                    "aliases": self.aliases,
                    "index_templates": self.index_templates,
                    "component_templates": self.component_templates,
                    "stored_scripts": self.stored_scripts,
                    "data_streams": self.data_streams,
                    "ilm_policies": self.ilm_policies,
                    "persistent_tasks": self.persistent_tasks,
                    "security": self.security,
                    "transforms": self.transforms,
                    "extras": self.extras,
                },
                fh,
            )
        os.replace(tmp, f)

    # ---- aliases ---------------------------------------------------------

    def put_alias(self, index: str, alias: str, props: dict | None = None):
        if alias in ("_all", "*") or not alias:
            raise IllegalArgumentError(f"invalid alias name [{alias}]")
        props = {k: v for k, v in (props or {}).items() if v is not None}
        self.aliases.setdefault(alias, {})[index] = props
        self.save()

    def remove_alias(self, index: str, alias_pattern: str, must_exist: bool = True):
        removed = False
        for alias in list(self.aliases):
            if not fnmatch.fnmatchcase(alias, alias_pattern):
                continue
            if index in self.aliases[alias]:
                del self.aliases[alias][index]
                removed = True
                if not self.aliases[alias]:
                    del self.aliases[alias]
        if not removed and must_exist:
            raise ResourceNotFoundError(
                f"aliases [{alias_pattern}] missing on index [{index}]"
            )
        self.save()
        return removed

    def drop_index(self, index: str):
        """Index deleted: remove it from every alias."""
        for alias in list(self.aliases):
            self.aliases[alias].pop(index, None)
            if not self.aliases[alias]:
                del self.aliases[alias]
        self.save()

    def aliases_of(self, index: str) -> dict[str, dict]:
        return {
            alias: members[index]
            for alias, members in self.aliases.items()
            if index in members
        }

    def write_index_of(self, alias: str) -> str:
        """Write resolution (reference behavior: IndexNameExpressionResolver
        WriteRequest resolution — a single-member alias is writable; a
        multi-member alias needs exactly one is_write_index=true)."""
        members = self.aliases[alias]
        if len(members) == 1:
            (index,) = members
            return index
        writers = [i for i, p in members.items() if p.get("is_write_index")]
        if len(writers) != 1:
            raise IllegalArgumentError(
                f"no write index is defined for alias [{alias}]. The write index may be "
                "explicitly disabled using is_write_index=false or the alias points to "
                "multiple indices without one being designated as a write index"
            )
        return writers[0]

    # ---- index name expression resolution --------------------------------

    def resolve_expression(
        self,
        expression,
        concrete: list[str],
        ignore_unavailable: bool = False,
        allow_no_indices: bool = True,
    ) -> list[str]:
        """Resolve a comma/list expression of names, wildcards, aliases and
        `-` exclusions to concrete index names, in stable (insertion) order.
        Reference behavior: IndexNameExpressionResolver.concreteIndexNames."""
        if expression is None or expression in ("", "_all", "*"):
            parts = ["*"]
        elif isinstance(expression, str):
            parts = [p for p in expression.split(",") if p]
        else:
            parts = list(expression)

        out: list[str] = []

        def add(name):
            if name not in out:
                out.append(name)

        def remove_matching(pattern):
            out[:] = [n for n in out if not fnmatch.fnmatchcase(n, pattern)]

        for part in parts:
            neg = part.startswith("-") and out  # leading '-' only excludes after an inclusion
            pat = part[1:] if neg else part
            if pat == "_all":
                pat = "*"
            is_pattern = "*" in pat or "?" in pat
            if neg:
                remove_matching(pat)
                # exclusions also strip alias-member expansions by alias name
                for alias, members in self.aliases.items():
                    if fnmatch.fnmatchcase(alias, pat):
                        for m in members:
                            if m in out:
                                out.remove(m)
                continue
            if is_pattern:
                for n in sorted(concrete):
                    if fnmatch.fnmatchcase(n, pat):
                        add(n)
                for alias in sorted(self.aliases):
                    if fnmatch.fnmatchcase(alias, pat):
                        for m in self.aliases[alias]:
                            add(m)
                for ds in sorted(self.data_streams):
                    if fnmatch.fnmatchcase(ds, pat):
                        for m in self.data_streams[ds]["indices"]:
                            add(m)
            elif pat in self.aliases:
                for m in self.aliases[pat]:
                    add(m)
            elif pat in self.data_streams:
                for m in self.data_streams[pat]["indices"]:
                    add(m)
            elif pat in concrete:
                add(pat)
            elif not ignore_unavailable:
                raise IndexNotFoundError(pat)
        if not out and not allow_no_indices:
            raise IndexNotFoundError(
                expression if isinstance(expression, str) else ",".join(parts)
            )
        return out

    def search_targets(
        self,
        expression,
        concrete: list[str],
        ignore_unavailable: bool = False,
        allow_no_indices: bool = True,
    ) -> list[tuple[str, dict | None]]:
        """Like resolve_expression but carries the alias filter when an index
        is reached *only* through filtered aliases (reference behavior:
        AliasFilter computation in TransportSearchAction — filters of all
        matching aliases are OR-combined; direct/unfiltered access wins)."""
        names = self.resolve_expression(
            expression, concrete, ignore_unavailable, allow_no_indices
        )
        if expression is None or expression in ("", "_all", "*"):
            return [(n, None) for n in names]
        parts = (
            [p for p in expression.split(",") if p]
            if isinstance(expression, str)
            else list(expression)
        )
        filters: dict[str, list] = {n: [] for n in names}
        unfiltered: set[str] = set()
        for part in parts:
            if part.startswith("-"):
                continue
            pat = "*" if part == "_all" else part
            is_pattern = "*" in pat or "?" in pat
            # direct index reference (or index wildcard match) = no filter
            for n in names:
                if (n == pat) or (is_pattern and fnmatch.fnmatchcase(n, pat)):
                    unfiltered.add(n)
            for alias, members in self.aliases.items():
                if alias == pat or (is_pattern and fnmatch.fnmatchcase(alias, pat)):
                    for m, props in members.items():
                        if m not in filters:
                            continue
                        f = props.get("filter")
                        if f:
                            filters[m].append(f)
                        else:
                            unfiltered.add(m)
        out = []
        for n in names:
            fs = filters.get(n) or []
            if n in unfiltered or not fs:
                out.append((n, None))
            elif len(fs) == 1:
                out.append((n, fs[0]))
            else:
                out.append((n, {"bool": {"should": fs, "minimum_should_match": 1}}))
        return out

    # ---- templates -------------------------------------------------------

    def put_index_template(self, name: str, body: dict):
        patterns = body.get("index_patterns")
        if not patterns:
            raise IllegalArgumentError("index template must have index_patterns")
        if isinstance(patterns, str):
            body = {**body, "index_patterns": [patterns]}
        for c in body.get("composed_of", []):
            if c not in self.component_templates:
                raise IllegalArgumentError(
                    f"index template [{name}] specifies component templates [{c}] that do not exist"
                )
        self.index_templates[name] = body
        self.save()

    def put_component_template(self, name: str, body: dict):
        if "template" not in body:
            raise IllegalArgumentError("component template must have a template")
        self.component_templates[name] = body
        self.save()

    def delete_index_template(self, name: str):
        matched = [t for t in self.index_templates if fnmatch.fnmatchcase(t, name)]
        if not matched:
            raise ResourceNotFoundError(f"index_template [{name}] missing")
        for t in matched:
            del self.index_templates[t]
        self.save()

    def delete_component_template(self, name: str):
        used_by = [
            t
            for t, b in self.index_templates.items()
            if name in b.get("composed_of", [])
        ]
        if used_by:
            raise IllegalArgumentError(
                f"component templates [{name}] cannot be removed as they are still in use "
                f"by index templates {sorted(used_by)}"
            )
        if name not in self.component_templates:
            raise ResourceNotFoundError(f"component_template [{name}] missing")
        del self.component_templates[name]
        self.save()

    def match_template(self, index_name: str) -> tuple[str, dict] | None:
        """Highest-priority matching composable template (reference behavior:
        MetadataIndexTemplateService.findV2Template)."""
        best = None
        for name, body in self.index_templates.items():
            if any(
                fnmatch.fnmatchcase(index_name, p) for p in body["index_patterns"]
            ):
                prio = body.get("priority", 0)
                if best is None or prio > best[0]:
                    best = (prio, name, body)
        if best is None:
            return None
        return best[1], best[2]

    def compose_for_index(self, index_name: str) -> dict:
        """Resolved {settings, mappings, aliases} for a new index: component
        templates in composed_of order, then the template's own overlay
        (reference behavior: MetadataIndexTemplateService.collectMappings /
        resolveSettings / resolveAliases)."""
        m = self.match_template(index_name)
        if m is None:
            return {}
        _, body = m
        out: dict = {"settings": {}, "mappings": {}, "aliases": {}}
        layers = [
            self.component_templates[c].get("template", {})
            for c in body.get("composed_of", [])
            if c in self.component_templates
        ]
        layers.append(body.get("template") or {})
        for layer in layers:
            out["settings"] = deep_merge(out["settings"], layer.get("settings") or {})
            out["mappings"] = deep_merge(out["mappings"], layer.get("mappings") or {})
            out["aliases"].update(layer.get("aliases") or {})
        if body.get("data_stream") is not None:
            out["data_stream"] = body["data_stream"]
        return out
