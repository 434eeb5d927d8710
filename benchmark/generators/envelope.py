"""A large cluster of many kinds at Kubernetes' documented envelope.

The configuration's ``inventory`` block gives every count and rate.
``n`` Pods are replicas of Deployments (one ReplicaSet each), sized as
clusterloader2's load profile sizes them (``deployments``: replica
classes and how many of each), spread over ``namespaces`` namespaces
whose Pod counts follow Zipf(``namespace_zipf_s``), capped at
``namespace_pod_cap``; ``system_namespace_p`` of the namespaces carry
the Pod Security Standards exemption label.  Every Pod copies its
Deployment's pod template, as a replica does: containers per template
are drawn from ``containers_per_pod``, and each field a policy reads is
drawn to violate at its rate in ``violation_p`` (per container for a
container's field, per pod template otherwise, per object elsewhere);
Pods in exempt namespaces draw the pod-security fields at
``system_violation_p``.  Services, ConfigMaps, Secrets (``data`` of
log-normal size, ``data_bytes``), Ingresses, Roles, RoleBindings,
PersistentVolumeClaims and PodDisruptionBudgets follow their counts.

The counts are the configuration's at ``pods`` Pods; another ``n``
scales each of them by ``n / pods`` (tests run it small).

Everything is drawn from one fixed seed, and the run's seed only draws
the numbers in the names, kept in the drawn order: every seed holds the
same objects in the same cache-key order under other names (as
``generators/pods.py`` does, and for the same reason: the order decides
which objects a capped audit reports).  Imports nothing of the program.
"""

from __future__ import annotations

import heapq
import pickle

import numpy as np

from harness.seeds import CONTENT, INVENTORY, rng

ALPHABET = np.array(list("bcdfghjklmnpqrstvwxz2456789"))  # names' suffixes
PSS_LABEL = "pod-security.kubernetes.io/enforce"
CPU_OK = ["100m", "250m", "500m", "1", "1500m", "2"]
# cpu requests that keep request <= 500m and limit / request <= 4
CPU_REQ = {"100m": ["100m"], "250m": ["100m", "250m"],
           "500m": ["250m", "500m"], "1": ["250m", "500m"],
           "1500m": ["500m"], "2": ["500m"]}
MEM_OK = ["64Mi", "96Mi", "100Mi"]
ENV_NAMES = ["MODE", "REGION", "LOG_LEVEL", "PORT", "WORKERS", "ZONE"]


class Draws:
    """Uniform draws and named rate checks over one numpy stream."""

    def __init__(self, g: np.random.Generator, rates: dict,
                 pss_p: float | None = None):
        self.g, self.rates, self.pss_p = g, rates, pss_p

    def hit(self, field: str) -> bool:
        """``field`` drawn to violate at its rate."""
        return self.chance(self.rates[field])

    def pss(self, field: str) -> bool:
        """A pod-security field: at ``pss_p`` where that is set (Pods in
        exempt namespaces), else at its rate."""
        return self.chance(self.rates[field] if self.pss_p is None
                           else self.pss_p)

    def chance(self, p: float) -> bool:
        return self.g.random() < p

    def pick(self, seq):
        return seq[int(self.g.integers(len(seq)))]

    def near(self, base: int, span: float) -> int:
        """A whole number in (base, base * (1 + span)]."""
        return base + 1 + int(self.g.integers(max(int(base * span), 1)))


def scaled(spec: dict) -> dict:
    """The counts of ``spec`` at ``n`` Pods."""
    f = spec["n"] / spec["pods"]
    out = {k: max(1, int(round(spec[k] * f)))
           for k in ("namespaces", "services", "configmaps", "secrets",
                     "ingresses", "roles", "rolebindings", "pvcs", "pdbs")}
    out["deployments"] = [max(1, int(round(c * f)))
                          for c in spec["deployments"]["count"]]
    return out


def _zipf_targets(g, n_ns: int, pods: int, s: float, cap: int):
    """Pods per namespace: Zipf(s) weights over randomly ranked
    namespaces, water-filled under ``cap``."""
    w = np.arange(1, n_ns + 1, dtype=float) ** -s
    w = w[g.permutation(n_ns)]
    target = np.zeros(n_ns)
    free = np.ones(n_ns, bool)
    left = float(pods)
    while True:
        t = left * w / w[free].sum()
        over = free & (t > cap)
        if not over.any():
            target[free] = t[free]
            return target
        target[over] = cap
        free &= ~over
        left -= cap * over.sum()


def _place(replicas: list[int], target: np.ndarray) -> list[int]:
    """A namespace per Deployment, largest first, each to the namespace
    with the most room left under its target."""
    heap = [(-t, k) for k, t in enumerate(target)]
    heapq.heapify(heap)
    out = [0] * len(replicas)
    for d in sorted(range(len(replicas)), key=lambda d: -replicas[d]):
        room, k = heapq.heappop(heap)
        out[d] = k
        heapq.heappush(heap, (room + replicas[d], k))
    return out


def _chars(codes: np.ndarray, width: int) -> list[str]:
    digits = (codes[:, None] // 27 ** np.arange(width)[::-1]) % 27
    return ["".join(r) for r in ALPHABET[digits]]


def _names(gs: np.random.Generator, prefix: str, n: int) -> list[str]:
    """``n`` names with distinct numbers drawn from the run's seed
    stream ``gs``, in increasing order."""
    nums = np.sort(gs.choice(10 * n, n, replace=False))
    return [f"{prefix}{v:07d}" for v in nums.tolist()]


def _container(d: Draws, j: int, team: int, app: int, spec: dict) -> dict:
    pss = d.pss
    near = spec["near_p"]
    span = spec["near_span"]
    img = f"gcr.io/team{team}/app{app}"
    digest = "@sha256:" + d.g.bytes(32).hex()
    if d.hit("image_docker_io"):
        image = f"docker.io/library/app{app}" + digest
    elif d.hit("image_latest"):
        image = img + ":latest"
    elif d.hit("image_untagged"):
        image = img
    elif d.hit("image_tag_only"):
        image = img + f":1.{int(d.g.integers(30))}.0"
    else:
        image = img + digest
    cpu_lim = d.pick(CPU_OK)
    cpu_req = d.pick(CPU_REQ[cpu_lim])
    if d.hit("cpu_limit_high"):
        cpu_lim = f"{d.near(2000, span)}m" if d.chance(near) else \
            d.pick(["2500m", "3", "4"])
        cpu_req = "500m"
    if d.hit("cpu_request_high"):
        cpu_lim = "2"
        cpu_req = f"{d.near(500, span)}m" if d.chance(near) else "1"
    if d.hit("cpu_ratio_high"):
        if d.chance(near):          # just above the ratio of 4
            cpu_lim, cpu_req = d.pick([("1", "249m"), ("2", "499m")])
        else:
            cpu_lim, cpu_req = "2", "100m"
    mem_lim = mem_req = d.pick(MEM_OK)
    if d.hit("memory_limit_high"):
        mem_lim = mem_req = f"{d.near(2048, span)}Mi" \
            if d.chance(near) else d.pick(["3Gi", "4Gi"])
    if d.hit("memory_request_high"):
        mem_lim = mem_req = d.pick(["128Mi", "256Mi", "512Mi", "1Gi"])
    if d.hit("memory_request_unequal"):
        if d.chance(near):          # unequal, within 1 %
            mem_lim, mem_req = d.pick([("514Mi", "512Mi"),
                                       ("1028Mi", "1024Mi")])
        else:
            mem_lim, mem_req = "100Mi", "64Mi"
    limits = {"cpu": cpu_lim, "memory": mem_lim, "ephemeral-storage": "1Gi"}
    requests = {"cpu": cpu_req, "memory": mem_req}
    if d.hit("ephemeral_missing"):
        del limits["ephemeral-storage"]
    if d.hit("limit_cpu_missing"):
        del limits["cpu"]
    if d.hit("limit_memory_missing"):
        del limits["memory"]
    if d.hit("request_cpu_missing"):
        del requests["cpu"]
    if d.hit("memory_request_missing"):
        requests.pop("memory", None)
    resources: dict = {"limits": limits, "requests": requests}
    if d.hit("limits_missing"):
        del resources["limits"]
    c: dict = {"name": "app" if j == 0 else f"sidecar{j}", "image": image}
    if not d.hit("resources_missing"):
        c["resources"] = resources
    pull = d.pick(["Always"] * 9 + [None])
    if d.hit("pull_policy_not_always"):
        pull = "IfNotPresent"
    if pull is not None:
        c["imagePullPolicy"] = pull
    port: dict = {"containerPort": 8080 + j, "protocol": "TCP"}
    if pss("host_port_low"):
        port["hostPort"] = d.pick([80, 443])
    elif pss("host_port_high"):
        port["hostPort"] = 40000 + j
    elif d.chance(0.01):
        port["hostPort"] = 8443 + j
    c["ports"] = [port]
    env = [{"name": ENV_NAMES[k], "value": "v"}
           for k in range(int(d.g.integers(0, 3)))]
    if d.hit("env_over_max"):
        env = [{"name": ENV_NAMES[k], "value": "v"}
               for k in range(int(d.g.integers(3, 6)))]
    if d.chance(0.05):              # secret-like, from a Secret: allowed
        env.append({"name": "API_TOKEN", "valueFrom": {"secretKeyRef": {
            "name": f"app{app}-token", "key": "token"}}})
    if d.hit("env_secret_value"):
        env.append({"name": d.pick(["DB_PASSWORD", "ApiKey", "SECRET"]),
                    "value": "hunter2"})
    if env:
        c["env"] = env
    probe = {"httpGet": {"path": "/healthz", "port": 8080 + j},
             "periodSeconds": 10}
    if not d.hit("liveness_missing"):
        c["livenessProbe"] = probe
    if not d.hit("readiness_missing"):
        c["readinessProbe"] = dict(probe)
    sc: dict = {"readOnlyRootFilesystem": not pss("readonly_rootfs_off"),
                "allowPrivilegeEscalation": pss("privilege_escalation"),
                "privileged": pss("privileged"),
                "capabilities": {"drop": ["ALL"]}}
    if pss("capability_disallowed"):
        sc["capabilities"]["add"] = d.pick([["NET_ADMIN"],
                                            ["SYS_ADMIN", "CHOWN"]])
    elif d.chance(0.05):
        sc["capabilities"]["add"] = ["NET_BIND_SERVICE"]
    c["securityContext"] = sc
    if d.hit("tty"):
        c["tty"] = True
    if d.hit("stdin"):
        c["stdin"] = True
    return c


def _pod_spec(d: Draws, team: int, app: int, ncont: int,
              spec: dict) -> dict:
    pss = d.pss
    out: dict = {"containers": [_container(d, j, team, app, spec)
                                for j in range(ncont)]}
    if d.chance(spec["init_container_p"]):
        image = (f"docker.io/library/init{app}:1.0"
                 if d.hit("init_image_docker_io") else
                 f"gcr.io/team{team}/init{app}@sha256:"
                 + d.g.bytes(32).hex())
        out["initContainers"] = [{"name": "init", "image": image}]
    sa = f"app{app}"
    if d.hit("service_account_default"):
        sa = "default"
    if not d.hit("service_account_missing"):
        out["serviceAccountName"] = sa
    out["automountServiceAccountToken"] = False
    if d.hit("automount_token"):
        out["automountServiceAccountToken"] = d.pick([True, None])
        if out["automountServiceAccountToken"] is None:
            del out["automountServiceAccountToken"]
    out["imagePullSecrets"] = [{"name": "regcred"}]
    if d.hit("pull_secrets_missing"):
        del out["imagePullSecrets"]
    elif d.hit("pull_secrets_empty"):
        out["imagePullSecrets"] = []
    if d.hit("priority_class_disallowed"):
        out["priorityClassName"] = "low"
    elif d.chance(0.7):
        out["priorityClassName"] = d.pick(["high", "default"])
    psc: dict = {"runAsUser": 1000 + int(d.g.integers(64000)),
                 "runAsNonRoot": not pss("run_as_non_root_off"),
                 "fsGroup": 2000,
                 "seccompProfile": {"type": d.pick(["RuntimeDefault"] * 4
                                                   + ["Localhost"])}}
    if pss("run_as_user_low"):
        psc["runAsUser"] = d.pick([0, 33, 999])
    elif pss("run_as_user_high"):
        psc["runAsUser"] = 70000
    if d.hit("seccomp_disallowed"):
        psc["seccompProfile"] = {"type": "Unconfined"}
    elif pss("seccomp_missing"):
        del psc["seccompProfile"]
    if pss("sysctl_forbidden"):
        psc["sysctls"] = [{"name": d.pick(["kernel.msgmax",
                                           "net.core.somaxconn"]),
                           "value": "65536"}]
    elif d.chance(0.01):
        psc["sysctls"] = [{"name": "net.ipv4.tcp_keepalive_time",
                           "value": "600"}]
    out["securityContext"] = psc
    if pss("host_pid"):
        out["hostPID"] = True
    if pss("host_ipc"):
        out["hostIPC"] = True
    if pss("host_network"):
        out["hostNetwork"] = True
    vols = [{"name": "config", "configMap": {"name": f"app{app}-config"}}]
    if pss("host_path_disallowed"):
        vols.append({"name": "host", "hostPath": {"path": d.pick(
            ["/etc/ssl", "/var/run/docker.sock", "/"])}})
    elif d.chance(0.01):
        vols.append({"name": "logs", "hostPath": {"path": "/var/log/app"}})
    out["volumes"] = vols
    return out


def make(seed: int, spec: dict) -> list[dict]:
    """The inventory of ``spec`` drawn from the fixed seed, named from
    ``seed``: Namespaces, then every namespaced kind."""
    g = rng(CONTENT, INVENTORY)
    gs = rng(seed, INVENTORY)
    d = Draws(g, spec["violation_p"])
    sys_d = Draws(g, spec["violation_p"], spec["system_violation_p"])
    cnt = scaled(spec)
    n_ns = cnt["namespaces"]
    rep_of = [r for r, c in zip(spec["deployments"]["replicas"],
                                cnt["deployments"]) for _ in range(c)]
    n_pods = sum(rep_of)
    target = _zipf_targets(g, n_ns, n_pods, spec["namespace_zipf_s"],
                           spec["namespace_pod_cap"])
    ns_of_dep = _place(rep_of, target)
    system = g.random(n_ns) < spec["system_namespace_p"]
    ns_names = _names(gs, "ns", n_ns)
    objs: list[dict] = []
    for k in range(n_ns):
        labels = {"kubernetes.io/metadata.name": ns_names[k],
                  "owner": f"team{k % 97}"}
        if d.hit("namespace_owner_missing"):
            del labels["owner"]
        if system[k]:
            labels[PSS_LABEL] = "privileged"
        elif d.chance(0.5):
            labels[PSS_LABEL] = d.pick(["restricted", "baseline"])
        objs.append({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": ns_names[k], "labels": labels}})

    # Deployments in namespace order, each with its ReplicaSet and Pods
    order = sorted(range(len(rep_of)), key=lambda i: (ns_of_dep[i], i))
    dep_names = _names(gs, "d", len(order))
    ncont_p = spec["containers_per_pod"]
    hashes = _chars(g.integers(0, 27 ** 10, len(order), dtype=np.int64), 10)
    for pos, i in enumerate(order):
        ns = ns_names[ns_of_dep[i]]
        name, reps, h = dep_names[pos], rep_of[i], hashes[pos]
        team, app = int(g.integers(40)), pos
        ncont = int(g.choice(ncont_p["values"], p=ncont_p["p"]))
        pod_spec = pickle.dumps(_pod_spec(
            sys_d if system[ns_of_dep[i]] else d, team, app, ncont,
            spec))
        env = "test" if d.hit("deployment_env_disallowed") else \
            d.pick(["prod", "dev", "staging"])
        labels = {"app": name, "team": f"team{team}", "env": env}
        pod_labels = {"app": name, "team": f"team{team}"}

        def template(**extra):
            return {"metadata": {"labels": dict(pod_labels, **extra)},
                    "spec": pickle.loads(pod_spec)}
        objs.append({"apiVersion": "apps/v1", "kind": "Deployment",
                     "metadata": {"name": name, "namespace": ns,
                                  "labels": labels},
                     "spec": {"replicas": reps,
                              "selector": {"matchLabels": {"app": name}},
                              "template": template()}})
        rs = f"{name}-{h}"
        objs.append({"apiVersion": "apps/v1", "kind": "ReplicaSet",
                     "metadata": {"name": rs, "namespace": ns,
                                  "labels": dict(pod_labels,
                                                 **{"pod-template-hash": h}),
                                  "ownerReferences": [{
                                      "apiVersion": "apps/v1",
                                      "kind": "Deployment", "name": name,
                                      "controller": True}]},
                     "spec": {"replicas": reps,
                              "template": template(
                                  **{"pod-template-hash": h})}})
        sfx = _chars(g.choice(27 ** 5, reps, replace=False), 5)
        nodes = g.integers(0, 5000, reps).tolist()
        for r in range(reps):
            p_spec = pickle.loads(pod_spec)
            p_spec["nodeName"] = f"node-{nodes[r]:04d}"
            objs.append({"apiVersion": "v1", "kind": "Pod",
                         "metadata": {"name": f"{rs}-{sfx[r]}",
                                      "namespace": ns,
                                      "labels": dict(pod_labels, **{
                                          "pod-template-hash": h}),
                                      "ownerReferences": [{
                                          "apiVersion": "apps/v1",
                                          "kind": "ReplicaSet", "name": rs,
                                          "controller": True}]},
                         "spec": p_spec})

    weights = np.bincount(ns_of_dep, weights=rep_of, minlength=n_ns) + 1.0
    weights /= weights.sum()

    def spread(kind: str, n: int) -> list[tuple[str, str]]:
        """(namespace, name) of ``n`` objects of ``kind``, spread over
        the namespaces as the Pods are, in cache-key order."""
        ks = np.sort(g.choice(n_ns, n, p=weights))
        names = _names(gs, kind, n)
        return [(ns_names[k], names[j]) for j, k in enumerate(ks.tolist())]

    for ns, name in spread("svc", cnt["services"]):
        svc_spec: dict = {"type": "ClusterIP", "selector": {"app": name},
                          "ports": [{"port": 80, "targetPort": 8080}]}
        if d.hit("service_node_port"):
            svc_spec["type"] = "NodePort"
        elif d.hit("service_load_balancer"):
            svc_spec["type"] = "LoadBalancer"
        if d.hit("service_external_ip_disallowed"):
            svc_spec["externalIPs"] = ["198.51.100.7"]
        elif d.chance(0.01):
            svc_spec["externalIPs"] = ["203.0.113.0"]
        meta = {"name": name, "namespace": ns, "labels": {"app": name}}
        if not d.hit("service_owner_annotation_missing"):
            meta["annotations"] = {"owner": f"team{int(g.integers(40))}"}
        objs.append({"apiVersion": "v1", "kind": "Service", "metadata": meta,
                     "spec": svc_spec})

    data_spec = spec["data_bytes"]
    sizes = np.minimum(np.exp(g.normal(np.log(data_spec["median"]),
                                       data_spec["sigma"],
                                       cnt["configmaps"] + cnt["secrets"])),
                       data_spec["cap"]).astype(np.int64)
    blob = "".join(np.array(list("ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmn"
                                 "opqrstuvwxyz0123456789+/"))[
        g.integers(0, 64, int(data_spec["cap"]) * 2)].tolist())
    k_data = 0
    for kind, key, n in (("ConfigMap", "cm", cnt["configmaps"]),
                         ("Secret", "sec", cnt["secrets"])):
        for ns, name in spread(key, n):
            size = int(sizes[k_data])
            k_data += 1
            parts = int(g.integers(1, data_spec["max_keys"] + 1))
            off = int(g.integers(0, data_spec["cap"]))
            step = -(-size // parts)
            data = {f"key{p}": blob[off + p * step: off + min(size,
                                                             (p + 1) * step)]
                    for p in range(parts)}
            o = {"apiVersion": "v1", "kind": kind,
                 "metadata": {"name": name, "namespace": ns}, "data": data}
            if kind == "Secret":
                o["type"] = "Opaque"
            objs.append(o)

    ing = spread("ing", cnt["ingresses"])
    hosts = [f"{name}.apps.example.com" for _, name in ing]
    n_dup = int(round(len(ing) * spec["violation_p"]["ingress_duplicate_host"]
                      / 2))
    if n_dup:
        pick = g.choice(len(ing), 2 * n_dup, replace=False)
        for a, b in zip(pick[:n_dup].tolist(), pick[n_dup:].tolist()):
            hosts[b] = hosts[a]
    for (ns, name), host in zip(ing, hosts):
        rule_host = f"*.{name}.example.com" \
            if d.hit("ingress_wildcard_host") else host
        backend = {"paths": [{"path": "/", "pathType": "Prefix", "backend": {
            "service": {"name": name, "port": {"number": 80}}}}]}
        rules: list[dict] = [{"host": rule_host, "http": backend}]
        if d.hit("ingress_rule_without_host"):
            rules.append({"http": backend})
        ing_spec: dict = {"host": host, "rules": rules,
                          "tls": [{"hosts": [host],
                                   "secretName": f"{name}-tls"}]}
        if d.hit("ingress_tls_missing"):
            del ing_spec["tls"]
        objs.append({"apiVersion": "networking.k8s.io/v1", "kind": "Ingress",
                     "metadata": {"name": name, "namespace": ns},
                     "spec": ing_spec})

    rbac = "rbac.authorization.k8s.io/v1"
    for ns, name in spread("role", cnt["roles"]):
        rules = [{"apiGroups": [""], "resources": ["pods", "services"],
                  "verbs": ["get", "list", "watch"]}
                 for _ in range(int(g.integers(1, 4)))]
        for field, key in (("role_wildcard_verbs", "verbs"),
                           ("role_wildcard_resources", "resources"),
                           ("role_wildcard_api_groups", "apiGroups")):
            if d.hit(field):
                rules[-1][key] = ["*"]
        objs.append({"apiVersion": rbac, "kind": "Role",
                     "metadata": {"name": name, "namespace": ns},
                     "rules": rules})
    for ns, name in spread("rb", cnt["rolebindings"]):
        subjects = [{"kind": "ServiceAccount", "name": f"app{k}",
                     "namespace": ns}
                    for k in range(int(g.integers(1, 3)))]
        if d.hit("rolebinding_anonymous"):
            subjects.append({"kind": "User", "name": "system:anonymous",
                             "apiGroup": "rbac.authorization.k8s.io"})
        if d.hit("rolebinding_unauthenticated"):
            subjects.append({"kind": "Group",
                             "name": "system:unauthenticated",
                             "apiGroup": "rbac.authorization.k8s.io"})
        objs.append({"apiVersion": rbac, "kind": "RoleBinding",
                     "metadata": {"name": name, "namespace": ns},
                     "roleRef": {"apiGroup": "rbac.authorization.k8s.io",
                                 "kind": "Role", "name": name},
                     "subjects": subjects})
    for ns, name in spread("pvc", cnt["pvcs"]):
        sc = "legacy-nfs" if d.hit("pvc_storage_class_disallowed") else \
            d.pick(["standard", "ssd"])
        objs.append({"apiVersion": "v1", "kind": "PersistentVolumeClaim",
                     "metadata": {"name": name, "namespace": ns},
                     "spec": {"accessModes": ["ReadWriteOnce"],
                              "storageClassName": sc,
                              "resources": {"requests": {
                                  "storage": d.pick(["10Gi", "50Gi"])}}}})
    for ns, name in spread("pdb", cnt["pdbs"]):
        pdb_spec: dict = d.pick([{"maxUnavailable": 1}, {"minAvailable": 1},
                                 {"minAvailable": "50%"}])
        if d.hit("pdb_max_unavailable_zero"):
            pdb_spec = {"maxUnavailable": 0}
        objs.append({"apiVersion": "policy/v1", "kind": "PodDisruptionBudget",
                     "metadata": {"name": name, "namespace": ns},
                     "spec": dict(pdb_spec,
                                  selector={"matchLabels": {"app": name}})})
    return objs
