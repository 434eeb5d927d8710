"""Plain reference for the gatekeeper-library policy set over a cluster
of many kinds.

Each template of ``configs/library-envelope-policy.json`` is written
again here from its Rego, in plain Python: a function of one object and
the constraint's parameters that returns the object's set of messages
(a Rego rule set holds each distinct violation once).  Matching is
written again from Gatekeeper's target (``match.kinds`` by API group
and kind; ``namespaceSelector`` against the cached Namespace's labels,
which a cluster-scoped object never matches).  It imports nothing of
the program.

It answers the capped audit: per constraint, the objects its match
selects in the order of their cache keys
(``namespace/<ns>/<apiVersion>/<kind>/<name>`` or
``cluster/<apiVersion>/<kind>/<name>``), all messages of each, taking
objects until at least ``limit`` messages are out.  Objects are
evaluated lazily in that order, so a capped audit reads only a prefix
of each constraint's objects.

The configuration states float32 for the quantity compares.  The control
is this reference computed one step below, in bfloat16: every cpu and
memory quantity, every policy maximum and the ratio rounded to bfloat16
before they are compared.

``LIMITS`` are the limits of the numbers that decide ``correct``.  Every
comparison is exact, so every limit is 0.
"""

from __future__ import annotations

import math
import re
import urllib.parse

from reference.pods import as_bfloat16, cpu_milli, mem_bytes

LIMITS = {
    "audit_missing": 0,      # reference entries the program left out
    "audit_extra": 0,        # program entries the reference does not have
}

UNDEF = object()


def get(x, *path):
    """``x.a.b...``: undefined past anything that is not an object."""
    for p in path:
        if not isinstance(x, dict) or p not in x:
            return UNDEF
        x = x[p]
    return x


def ok(v) -> bool:
    """Rego statement truth: defined and not false."""
    return v is not UNDEF and v is not False


def each(x, *path) -> list:
    """``x.a.b[_]``: the elements of an array, or the values of an
    object; nothing for anything else."""
    v = get(x, *path)
    if isinstance(v, list):
        return v
    if isinstance(v, dict):
        return list(v.values())
    return []


def vstr(v, top: bool = True) -> str:
    """sprintf ``%v``."""
    if v is None:
        return "null"
    if v is True:
        return "true"
    if v is False:
        return "false"
    if isinstance(v, str):
        return v if top else '"' + v + '"'
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    if isinstance(v, (int, float)):
        return str(v)
    if isinstance(v, (set, frozenset)):
        if not v:
            return "set()"
        return "{" + ", ".join(vstr(x, False) for x in sorted(v)) + "}"
    if isinstance(v, list):
        return "[" + ", ".join(vstr(x, False) for x in v) + "]"
    raise ValueError(f"no %v for {v!r}")


def cpu(v, q):
    """canonify_cpu, after ``q``; None where undefined."""
    x = cpu_milli(v) if v is not UNDEF else math.nan
    return None if math.isnan(x) else q(x)


def mem(v, q):
    """canonify_mem, after ``q``; None where undefined."""
    x = mem_bytes(v) if v is not UNDEF else math.nan
    return None if math.isnan(x) else q(x)


def gt(a, b) -> bool:
    return a is not None and b is not None and a > b


def containers(obj) -> list:
    """The containers whose name is defined: every container template
    puts ``container.name`` in its message, which is undefined without
    it."""
    return [c for c in each(obj, "spec", "containers")
            if get(c, "name") is not UNDEF]


def cname(c) -> str:
    return vstr(get(c, "name"))


def images(obj):
    """(container, image) of each container with a string image (the
    templates' positive image tests are undefined on anything else)."""
    for c in containers(obj):
        img = get(c, "image")
        if isinstance(img, str):
            yield c, img


# ---------------------------------------------------------------- templates
# each: (object, parameters, q) -> set of messages


def required_labels(obj, p, q):
    labels = get(obj, "metadata", "labels")
    have = {k for k, v in labels.items() if ok(v)} \
        if isinstance(labels, dict) else set()
    missing = set(p["labels"]) - have
    return {f"you must provide labels: {vstr(missing)}"} if missing else set()


def required_annotations(obj, p, q):
    ann = get(obj, "metadata", "annotations")
    have = {k for k, v in ann.items() if ok(v)} \
        if isinstance(ann, dict) else set()
    missing = set(p["annotations"]) - have
    return {f"missing required annotations: {vstr(missing)}"} \
        if missing else set()


def valid_label_value(obj, p, q):
    v = get(obj, "metadata", "labels", p["key"])
    if v is UNDEF or v in p["allowed"]:
        return set()
    return {f"label <{vstr(p['key'])}> value <{vstr(v)}> is not allowed"}


def allowed_repos(obj, p, q):
    out = set()
    for field, what in (("containers", "container"),
                        ("initContainers", "initContainer")):
        for c in each(obj, "spec", field):
            img = get(c, "image")
            if img is UNDEF or get(c, "name") is UNDEF:
                continue        # the message is undefined
            if not (isinstance(img, str) and any(img.startswith(r)
                                        for r in p["repos"])):
                out.add(f"{what} <{cname(c)}> has an invalid image repo "
                        f"<{vstr(img)}>")
    return out


def disallowed_tags(obj, p, q):
    out = {f"container <{cname(c)}> uses a disallowed tag <{vstr(tag)}>"
           for c, img in images(obj) for tag in p["tags"]
           if img.endswith(":" + tag)}
    for c in containers(obj):
        img = get(c, "image")
        if not (isinstance(img, str) and ":" in img):    # `not contains(...)`
            out.add(f"container <{cname(c)}> has no image tag")
    return out


_DIGEST = re.compile(r"@sha256:[a-f0-9]{64}$")


def image_digests(obj, p, q):
    out = set()
    for c in containers(obj):
        img = get(c, "image")
        if img is not UNDEF and not (isinstance(img, str)
                                     and _DIGEST.search(img)):
            out.add(f"container <{cname(c)}> image <{vstr(img)}> is not "
                    f"pinned by digest")
    return out


def container_limits(obj, p, q):
    out = set()
    max_cpu = cpu(p["cpu"], q)
    max_mem = mem(p["memory"], q)
    for c in containers(obj):
        lim = get(c, "resources", "limits")
        v = get(lim, "cpu")
        if v is not UNDEF and gt(cpu(v, q), max_cpu):
            out.add(f"container <{cname(c)}> cpu limit <{vstr(v)}> is "
                    f"higher than the maximum allowed of <{vstr(p['cpu'])}>")
        v = get(lim, "memory")
        if v is not UNDEF and gt(mem(v, q), max_mem):
            out.add(f"container <{cname(c)}> memory limit <{vstr(v)}> is "
                    f"higher than the maximum allowed of "
                    f"<{vstr(p['memory'])}>")
        if not ok(lim):
            out.add(f"container <{cname(c)}> has no resource limits")
    return out


def container_requests(obj, p, q):
    out = set()
    for c in containers(obj):
        req = get(c, "resources", "requests")
        if gt(cpu(get(req, "cpu"), q), cpu(p["cpu"], q)):
            out.add(f"container <{cname(c)}> cpu request is too high")
        if gt(mem(get(req, "memory"), q), mem(p["memory"], q)):
            out.add(f"container <{cname(c)}> memory request is too high")
    return out


def container_ratios(obj, p, q):
    out = set()
    for c in containers(obj):
        limit = cpu(get(c, "resources", "limits", "cpu"), q)
        request = cpu(get(c, "resources", "requests", "cpu"), q)
        if limit is not None and request is not None and request > 0 \
                and limit / request > q(p["ratio"]):
            out.add(f"container <{cname(c)}> cpu limit/request ratio is "
                    f"too high")
    return out


def max_containers(obj, p, q):
    cs = get(obj, "spec", "containers")
    if not isinstance(cs, (list, dict, str)):
        return set()
    n = len(cs)
    return {f"too many containers: {n}"} if n > p["max"] else set()


def replica_limits(obj, p, q):
    r = get(obj, "spec", "replicas")
    if not isinstance(r, (int, float)) or isinstance(r, bool):
        return set()
    out = set()
    if r > p["max"]:
        out.add(f"replica count {vstr(r)} is above the maximum "
                f"{vstr(p['max'])}")
    if r < p["min"]:
        out.add(f"replica count {vstr(r)} is below the minimum "
                f"{vstr(p['min'])}")
    return out


def required_probes(obj, p, q):
    return {f"container <{cname(c)}> has no <{vstr(probe)}>"
            for c in containers(obj) for probe in p["probes"]
            if not ok(get(c, probe))}


def privileged(obj, p, q):
    return {f"privileged container is not allowed: {cname(c)}"
            for c in containers(obj)
            if ok(get(c, "securityContext", "privileged"))}


def read_only_root_fs(obj, p, q):
    return {f"container <{cname(c)}> must set readOnlyRootFilesystem"
            for c in containers(obj)
            if not ok(get(c, "securityContext", "readOnlyRootFilesystem"))}


def allow_privilege_escalation(obj, p, q):
    return {f"container <{cname(c)}> must not allow privilege escalation"
            for c in containers(obj)
            if ok(get(c, "securityContext", "allowPrivilegeEscalation"))}


def capabilities(obj, p, q):
    return {f"container <{cname(c)}> adds disallowed capability "
            f"<{vstr(cap)}>"
            for c in containers(obj)
            for cap in each(c, "securityContext", "capabilities", "add")
            if cap in p["disallowed"]}


def _number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def allowed_users(obj, p, q):
    uid = get(obj, "spec", "securityContext", "runAsUser")
    if not _number(uid):
        return set()
    out = set()
    if uid < p["min"]:
        out.add(f"runAsUser {vstr(uid)} is below the minimum")
    if uid > p["max"]:
        out.add(f"runAsUser {vstr(uid)} is above the maximum")
    return out


def require_run_as_non_root(obj, p, q):
    if ok(get(obj, "spec", "securityContext", "runAsNonRoot")):
        return set()
    name = get(obj, "metadata", "name")
    return set() if name is UNDEF else \
        {f"pod <{vstr(name)}> must set runAsNonRoot"}


def host_namespaces(obj, p, q):
    out = set()
    if ok(get(obj, "spec", "hostPID")):
        out.add("sharing the host PID namespace is not allowed")
    if ok(get(obj, "spec", "hostIPC")):
        out.add("sharing the host IPC namespace is not allowed")
    return out


def host_network(obj, p, q):
    return {"host network is not allowed"} \
        if ok(get(obj, "spec", "hostNetwork")) else set()


def host_filesystem(obj, p, q):
    out = set()
    for vol in each(obj, "spec", "volumes"):
        path = get(vol, "hostPath", "path")
        if path is UNDEF or get(vol, "name") is UNDEF:
            continue
        if not (isinstance(path, str) and any(path.startswith(a)
                                     for a in p["allowedPaths"])):
            out.add(f"hostPath volume <{vstr(get(vol, 'name'))}> at "
                    f"<{vstr(path)}> is not allowed")
    return out


def _host_ports(obj):
    for c in containers(obj):
        for port in each(c, "ports"):
            hp = get(port, "hostPort")
            if _number(hp):
                yield c, hp


def host_ports(obj, p, q):
    return {f"container <{cname(c)}> hostPort {vstr(hp)} is below the "
            f"allowed range"
            for c, hp in _host_ports(obj) if hp < p["min"]}


def allowed_host_ports(obj, p, q):
    return {f"container <{cname(c)}> hostPort <{vstr(hp)}> is outside the "
            f"allowed range"
            for c, hp in _host_ports(obj) if hp < p["min"] or hp > p["max"]}


def block_node_port(obj, p, q):
    return {"NodePort services are not allowed"} \
        if get(obj, "spec", "type") == "NodePort" else set()


def block_load_balancer(obj, p, q):
    return {"LoadBalancer services are not allowed"} \
        if get(obj, "spec", "type") == "LoadBalancer" else set()


def external_ips(obj, p, q):
    return {f"externalIP <{vstr(ip)}> is not allowed"
            for ip in each(obj, "spec", "externalIPs")
            if ip not in p["allowedIPs"]}


def https_only(obj, p, q):
    if get(obj, "kind") != "Ingress" or ok(get(obj, "spec", "tls")):
        return set()
    name = get(obj, "metadata", "name")
    return set() if name is UNDEF else \
        {f"ingress <{vstr(name)}> must be https-only (spec.tls required)"}


def block_wildcard_ingress(obj, p, q):
    out = set()
    for rule in each(obj, "spec", "rules"):
        host = get(rule, "host")
        if isinstance(host, str) and "*" in host:
            out.add(f"wildcard ingress host <{host}> is not allowed")
        if not ok(host):
            out.add("ingress rule without a host is not allowed")
    return out


def no_env_var_secrets(obj, p, q):
    pattern = re.compile(p["pattern"])
    return {f"container <{cname(c)}> passes secret-like env var "
            f"<{vstr(get(e, 'name'))}> by value"
            for c in containers(obj) for e in each(c, "env")
            if isinstance(get(e, "name"), str)
            and pattern.search(get(e, "name"))
            and ok(get(e, "value"))}


def disallowed_anonymous(obj, p, q):
    names = {get(s, "name") for s in each(obj, "subjects")}
    return {f"binding to {who} is not allowed"
            for who in ("system:anonymous", "system:unauthenticated")
            if who in names}


def image_pull_policy(obj, p, q):
    return {f"container <{cname(c)}> imagePullPolicy must be "
            f"{vstr(p['policy'])}"
            for c in containers(obj)
            if get(c, "imagePullPolicy") not in (UNDEF, p["policy"])}


def required_service_account(obj, p, q):
    out = set()
    sa = get(obj, "spec", "serviceAccountName")
    if sa is not UNDEF and sa in p["disallowed"]:
        out.add(f"service account <{vstr(sa)}> is not allowed")
    if not ok(sa) and get(obj, "kind") == "Pod":
        out.add("an explicit serviceAccountName is required")
    return out


def disallowed_repos(obj, p, q):
    return {f"container <{cname(c)}> image <{img}> comes from a "
            f"disallowed repository <{vstr(repo)}>"
            for c, img in images(obj) for repo in p["repos"]
            if img.startswith(repo)}


def forbidden_sysctls(obj, p, q):
    return {f"sysctl <{vstr(get(e, 'name'))}> is forbidden"
            for e in each(obj, "spec", "securityContext", "sysctls")
            if get(e, "name") in p["sysctls"]}


def ephemeral_storage_limit(obj, p, q):
    return {f"container <{cname(c)}> has no ephemeral-storage limit"
            for c in containers(obj)
            if not ok(get(c, "resources", "limits", "ephemeral-storage"))}


def automount_service_account_token(obj, p, q):
    if get(obj, "kind") == "Pod" and \
            get(obj, "spec", "automountServiceAccountToken") is not False:
        return {"automountServiceAccountToken must be set to false"}
    return set()


def allowed_seccomp_profiles(obj, p, q):
    out = set()
    ptype = get(obj, "spec", "securityContext", "seccompProfile", "type")
    if ptype is not UNDEF and ptype not in p["profiles"]:
        out.add(f"seccomp profile <{vstr(ptype)}> is not allowed")
    if get(obj, "kind") == "Pod" and \
            not ok(get(obj, "spec", "securityContext", "seccompProfile")):
        out.add("a pod-level seccompProfile is required")
    return out


def disallow_latest_tag(obj, p, q):
    return {f"container <{cname(c)}> uses the mutable :latest tag"
            for c, img in images(obj) if img.endswith(":latest")}


def disallow_interactive_tty(obj, p, q):
    out = set()
    for c in containers(obj):
        if get(c, "tty") is True:
            out.add(f"container <{cname(c)}> must not allocate a TTY")
        if get(c, "stdin") is True:
            out.add(f"container <{cname(c)}> must not keep stdin open")
    return out


def pod_disruption_budget(obj, p, q):
    mu = get(obj, "spec", "maxUnavailable")
    if get(obj, "kind") == "PodDisruptionBudget" and _number(mu) and mu == 0:
        return {"PodDisruptionBudget with maxUnavailable 0 blocks all "
                "evictions"}
    return set()


def storage_class(obj, p, q):
    sc = get(obj, "spec", "storageClassName")
    if get(obj, "kind") != "PersistentVolumeClaim" or sc is UNDEF \
            or sc in p["allowedStorageClasses"]:
        return set()
    return {f"storageClassName <{vstr(sc)}> is not allowed"}


def required_resources(obj, p, q):
    return {f"container <{cname(c)}> has no resources.{what}.{vstr(f)}"
            for c in containers(obj)
            for what in ("limits", "requests") for f in p.get(what, [])
            if not ok(get(c, "resources", what, f))}


def priority_class(obj, p, q):
    pc = get(obj, "spec", "priorityClassName")
    if get(obj, "kind") != "Pod" or pc is UNDEF or pc in p["allowed"]:
        return set()
    return {f"priorityClassName <{vstr(pc)}> is not allowed"}


def image_pull_secrets(obj, p, q):
    if get(obj, "kind") != "Pod":
        return set()
    s = get(obj, "spec", "imagePullSecrets")
    out = set()
    if not ok(s):
        out.add("pod must specify imagePullSecrets")
    if isinstance(s, (list, dict, str)) and len(s) == 0:
        out.add("pod must specify at least one imagePullSecret")
    return out


def prohibit_role_wildcard_access(obj, p, q):
    name = get(obj, "metadata", "name")
    out = set()
    for rule in each(obj, "rules"):
        for field, what in (("verbs", "verbs"), ("resources", "resources"),
                            ("apiGroups", "apiGroups")):
            if "*" in each(rule, field):
                out.add(f"role <{vstr(name)}> grants wildcard {what}")
    return out if name is not UNDEF else set()


def memory_request_equals_limit(obj, p, q):
    out = set()
    for c in containers(obj):
        req_v = get(c, "resources", "requests", "memory")
        lim_v = get(c, "resources", "limits", "memory")
        req, lim = mem(req_v, q), mem(lim_v, q)
        if req is not None and lim is not None and req != lim:
            out.add(f"container <{cname(c)}> memory request must equal its "
                    f"limit")
        if ok(lim_v) and not ok(req_v):
            out.add(f"container <{cname(c)}> sets a memory limit but no "
                    f"memory request")
    return out


def container_env_max_vars(obj, p, q):
    out = set()
    for c in containers(obj):
        env = get(c, "env")
        if isinstance(env, (list, dict, str)) and len(env) > p["max"]:
            out.add(f"container <{cname(c)}> has more than {vstr(p['max'])} "
                    f"env vars")
    return out


TEMPLATES = {
    "K8sAllowPrivilegeEscalation": allow_privilege_escalation,
    "K8sAllowedHostPorts": allowed_host_ports,
    "K8sAllowedRepos": allowed_repos,
    "K8sAllowedSeccompProfiles": allowed_seccomp_profiles,
    "K8sAllowedUsers": allowed_users,
    "K8sAutomountServiceAccountToken": automount_service_account_token,
    "K8sBlockLoadBalancer": block_load_balancer,
    "K8sBlockNodePort": block_node_port,
    "K8sBlockWildcardIngress": block_wildcard_ingress,
    "K8sCapabilities": capabilities,
    "K8sContainerEnvMaxVars": container_env_max_vars,
    "K8sContainerLimits": container_limits,
    "K8sContainerRatios": container_ratios,
    "K8sContainerRequests": container_requests,
    "K8sDisallowInteractiveTTY": disallow_interactive_tty,
    "K8sDisallowLatestTag": disallow_latest_tag,
    "K8sDisallowedAnonymous": disallowed_anonymous,
    "K8sDisallowedRepos": disallowed_repos,
    "K8sDisallowedTags": disallowed_tags,
    "K8sEphemeralStorageLimit": ephemeral_storage_limit,
    "K8sExternalIPs": external_ips,
    "K8sForbiddenSysctls": forbidden_sysctls,
    "K8sHostFilesystem": host_filesystem,
    "K8sHostNamespaces": host_namespaces,
    "K8sHostNetwork": host_network,
    "K8sHostPorts": host_ports,
    "K8sHttpsOnly": https_only,
    "K8sImageDigests": image_digests,
    "K8sImagePullPolicy": image_pull_policy,
    "K8sImagePullSecrets": image_pull_secrets,
    "K8sMaxContainers": max_containers,
    "K8sMemoryRequestEqualsLimit": memory_request_equals_limit,
    "K8sNoEnvVarSecrets": no_env_var_secrets,
    "K8sPodDisruptionBudget": pod_disruption_budget,
    "K8sPriorityClass": priority_class,
    "K8sPrivileged": privileged,
    "K8sProhibitRoleWildcardAccess": prohibit_role_wildcard_access,
    "K8sReadOnlyRootFS": read_only_root_fs,
    "K8sReplicaLimits": replica_limits,
    "K8sRequireRunAsNonRoot": require_run_as_non_root,
    "K8sRequiredAnnotations": required_annotations,
    "K8sRequiredLabels": required_labels,
    "K8sRequiredProbes": required_probes,
    "K8sRequiredResources": required_resources,
    "K8sRequiredServiceAccount": required_service_account,
    "K8sStorageClass": storage_class,
    "K8sValidLabelValue": valid_label_value,
    # K8sUniqueIngressHost joins the inventory: LibraryReference._unique_host
}


# ---------------------------------------------------------------- matching


def cache_key(obj: dict) -> str:
    meta = obj["metadata"]
    api = urllib.parse.quote(obj["apiVersion"], safe="")
    ns = meta.get("namespace")
    if ns:
        return f"namespace/{ns}/{api}/{obj['kind']}/{meta['name']}"
    return f"cluster/{api}/{obj['kind']}/{meta['name']}"


def group_of(api_version: str) -> str:
    return api_version.split("/")[0] if "/" in api_version else ""


def _expr_violated(expr: dict, labels: dict) -> bool:
    op, key = expr.get("operator", ""), expr.get("key", "")
    values = expr.get("values") or []
    if op == "In":
        return key not in labels or (values and labels[key] not in values)
    if op == "NotIn":
        return key in labels and bool(values) and labels[key] in values
    if op == "Exists":
        return key not in labels
    if op == "DoesNotExist":
        return key in labels
    return False


def selector_matches(sel: dict, labels: dict) -> bool:
    if any(labels.get(k) != v
           for k, v in (sel.get("matchLabels") or {}).items()):
        return False
    return not any(_expr_violated(e, labels)
                   for e in sel.get("matchExpressions") or [])


class LibraryReference:
    """The capped audit of an inventory under the policy set, its
    quantities compared after ``q``."""

    def __init__(self, policy: dict, inventory: list[dict], q):
        self.constraints = policy["constraints"]
        self.q = q
        self.objs = sorted(inventory, key=cache_key)
        self.ns_labels = {
            o["metadata"]["name"]: o["metadata"].get("labels") or {}
            for o in self.objs if o["kind"] == "Namespace"
            and o["apiVersion"] == "v1"}
        self._rows: dict[str, list[int]] = {}
        self.by_kind: dict[tuple[str, str], list[int]] = {}
        for i, o in enumerate(self.objs):
            self.by_kind.setdefault((group_of(o["apiVersion"]), o["kind"]),
                                    []).append(i)
        # K8sUniqueIngressHost: the names of the namespaced Ingresses of
        # any apiVersion holding each spec.host
        self.host_names: dict = {}
        for o in self.objs:
            if o["kind"] == "Ingress" and o["metadata"].get("namespace"):
                h = get(o, "spec", "host")
                if h is not UNDEF:
                    self.host_names.setdefault(_hashable(h), set()).add(
                        o["metadata"]["name"])

    def _unique_host(self, obj, p, q):
        host = get(obj, "spec", "host")
        if host is UNDEF:
            return set()
        names = self.host_names.get(_hashable(host), set())
        if names - {get(obj, "metadata", "name")}:
            return {f"duplicate ingress host {vstr(host)}"}
        return set()

    def _candidates(self, match: dict) -> list[int]:
        key = repr(match.get("kinds", "*"))
        hit = self._rows.get(key)
        if hit is None:
            hit = self._rows[key] = self._select(match)
        return hit

    def _select(self, match: dict) -> list[int]:
        if "kinds" not in match:
            return list(range(len(self.objs)))
        rows: set[int] = set()
        for ks in match["kinds"] or []:
            groups, kinds = ks.get("apiGroups") or [], ks.get("kinds") or []
            for (g, k), idx in self.by_kind.items():
                if ("*" in groups or g in groups) and \
                        ("*" in kinds or k in kinds):
                    rows.update(idx)
        return sorted(rows)

    def _ns_ok(self, match: dict, obj: dict) -> bool:
        sel = match.get("namespaceSelector")
        if sel is None:
            return True
        labels = self.ns_labels.get(obj["metadata"].get("namespace") or "")
        return labels is not None and selector_matches(sel, labels)

    def audit(self, limit: int) -> list[tuple]:
        """(constraint name, namespace, name, msg) of the capped audit."""
        out = []
        for c in self.constraints:
            kind = c["kind"]
            fn = self._unique_host if kind == "K8sUniqueIngressHost" \
                else TEMPLATES[kind]
            params = (c.get("spec") or {}).get("parameters") or {}
            match = (c.get("spec") or {}).get("match") or {}
            if set(match) - {"kinds", "namespaceSelector"}:
                raise ValueError(f"{c['metadata']['name']}: unsupported "
                                 f"match fields {sorted(match)}")
            name = c["metadata"]["name"]
            emitted = 0
            for i in self._candidates(match):
                if emitted >= limit:
                    break
                obj = self.objs[i]
                if not self._ns_ok(match, obj):
                    continue
                msgs = fn(obj, params, self.q)
                meta = obj["metadata"]
                out.extend((name, meta.get("namespace"), meta["name"], m)
                           for m in sorted(msgs))
                emitted += len(msgs)
        return out


def _hashable(v):
    return v if isinstance(v, (str, int, float, bool, type(None))) \
        else repr(v)


def exact(x):
    """Compared as it is: every quantity drawn is exact in float32, so
    float32 and the float64 here compare alike."""
    return x


def as_bf16(x):
    return float(as_bfloat16(x))


def make(policy: dict, inventory: list[dict], control: bool = False):
    """The reference over ``inventory``; ``control`` computes it in
    bfloat16, as the module's docstring says."""
    return LibraryReference(policy, inventory, as_bf16 if control else exact)
