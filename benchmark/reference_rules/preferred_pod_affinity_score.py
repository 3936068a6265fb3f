"""The first rule of the plain reference that compares scores: each bind
goes to a node whose default-profile score (kubernetes v1.18) is the
highest, within EPSILON points, among the nodes feasible at its commit,
scored on an occupancy that the wave kernel may have scored its launch on.

Plain Python and numpy in float64 over the manifests and the binds
replayed so far; nothing of the program is imported. State lives in
`cluster.rule_state["preferred_pod_affinity_score"]`.

The score of a pod on a node, every plugin of v1.18's default profile at
weight 1 but NodePreferAvoidPods at 10,000 (requests are the non-zero
requests: 100m cpu and 200Mi memory for a container that states none):

    LeastAllocated      mean over cpu and memory of (alloc - req) / alloc x 100
    BalancedAllocation  (1 - |cpu fraction - memory fraction|) x 100
    InterPodAffinity    +w for each pod in the node's domain that matches one
                        of the pod's preferred affinity terms (-w: preferred
                        anti-affinity); +w for each existing pod whose
                        preferred affinity term the pod matches (-w: its
                        preferred anti-affinity; +1, the hard pod affinity
                        weight, for its required affinity term), in that
                        pod's domain; normalised over the feasible nodes as
                        v1.18's NormalizeScore does, (s - min) / (max - min)
                        x 100 with min <= 0 <= max
    ImageLocality 0, NodeAffinity 0, TaintToleration 100, NodePreferAvoidPods
    100, DefaultPodTopologySpread 100, PodTopologySpread 100: what they give
    every node alike for a pod without images, node affinity, services,
    spread constraints or controller, on nodes without taints or
    preferAvoidPods annotations

Staleness (ops/wavelattice.py's documented score model): a launch is
scored once, on the occupancy at its start, and feasibility is checked
again at each commit. Launches commit in order, and each launch's
snapshot holds every earlier launch's commits, so the occupancy a launch
was scored on is a prefix of the commit order: state s, the first s binds
replayed. The rule keeps an anchor `a`. Bind t (the (t+1)-th replayed)
must score within EPSILON of the best score under state a, the best taken
over the nodes feasible under state t; if it does not, the anchor moves to
the smallest a' in (a, t] under which it does, and if there is none the
bind is refused. The anchor is never older than two bounds: a launch of
the runs this rule judges carries at most LAUNCH_MAX pods, so its start
lies at most LAUNCH_MAX - 1 binds before any of its own; and a pod
created bound is there before the scheduler's first launch (the harness
loads the residents before it starts the scheduler), so every launch's
start lies after the last of them. This refuses no bind that the
kernel's model admits in a launch of at most LAUNCH_MAX pods (each
launch's start is such an a', and the greedy anchor never passes it),
and it refuses a bind that is best under no recent state.

EPSILON: the kernel adds NodePreferAvoidPods' 10,000 x 100 to every
node's float32 total, where float32 steps by 0.0625, and its weighted sum
over 13 components rounds a few times: it cannot order two nodes closer
than about 0.25 points, and neither may this rule.

Departures from upstream, each because the configurations that name this
rule cannot show the difference or the kernel's model is the yardstick:
  * no per-plugin integer truncation: the kernel's scores are real-valued;
  * min-max normalisation (upstream's) and the kernel's max-|s|
    normalisation agree only where no score is negative: the
    configurations that name this rule have preferred affinity terms
    alone, and their sums are never negative;
  * BalancedAllocation's 0 for a node whose cpu or memory the pod fills
    to the last unit is left out, as the kernel and the host plugin
    leave it out (a fraction is capped at 1);
  * the feasible nodes are those of harness/check.py's four rules
    (schedulable; pods, cpu, memory; the pod's own required pod
    (anti-)affinity); another rule the configuration names is not
    consulted;
  * a selector is its `matchLabels` alone, and no pod is terminating;
  * a pod created bound (`spec.nodeName` in its manifest) is not
    judged: no scheduler placed it. It is counted like every other.
"""

import numpy as np

NAME = "preferred_pod_affinity_score"
EPSILON = 0.25
# the most pods one launch carries in a run this rule judges. The
# scheduler's batch bucket (4,096) bounds any launch; the largest a run of
# the configurations that name this rule makes is its warm-up's burst
# (600 pods; the harness's `largest_batch` reads 593-595 on a TPU v5e),
# and the window's launches carry what arrived since the last (~13). The
# bucket would leave the first 4,096 binds past the residents unjudged,
# and every later one judged on states up to 4,095 binds old. A run whose
# `largest_batch` exceeds this may see a bind refused that its launch's
# start admits
LAUNCH_MAX = 1024
# refusals after which a bind the anchor does not admit is refused
# without a search for a later state: the run is not correct by then,
# and a search costs up to LAUNCH_MAX scores a bind
SEARCHED_REFUSALS = 16

W_PREFER_AVOID = 10000.0
HARD_POD_AFFINITY_WEIGHT = 1.0
# TaintToleration + NodePreferAvoidPods + DefaultPodTopologySpread +
# PodTopologySpread; ImageLocality and NodeAffinity give 0
CONSTANT = 100.0 + W_PREFER_AVOID * 100.0 + 100.0 + 100.0
DEFAULT_MILLI_CPU = 100
DEFAULT_MEMORY = 200 * 1024 * 1024

_SUFFIX = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40,
           "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12}
_SIGN = {"aff_pref": 1.0, "anti_pref": -1.0, "aff_req": HARD_POD_AFFINITY_WEIGHT}


def _quantity(v, milli=False) -> float:
    s = str(v).strip()
    scale = 1000 if milli else 1
    if s.endswith("m"):
        return float(s[:-1]) * scale / 1000
    for suf, mult in _SUFFIX.items():
        if s.endswith(suf):
            return float(s[: -len(suf)]) * mult * scale
    return float(s) * scale


def _selector(term: dict) -> frozenset:
    ml = (term.get("labelSelector") or {}).get("matchLabels") or {}
    return frozenset(dict(ml).items())


class _Pod:
    """What a score or a filter reads of one manifest."""

    def __init__(self, manifest: dict):
        meta = manifest.get("metadata") or {}
        spec = manifest.get("spec") or {}
        self.ns = meta.get("namespace", "")
        self.labels = dict(meta.get("labels") or {})
        self.created_bound = bool(spec.get("nodeName"))
        req, nz = [0.0, 0.0], [0.0, 0.0]
        for c in spec.get("containers") or []:
            r = c.get("requests") or {}
            for i, (name, default) in enumerate(
                    (("cpu", DEFAULT_MILLI_CPU), ("memory", DEFAULT_MEMORY))):
                v = _quantity(r[name], milli=(i == 0)) if name in r else 0.0
                req[i] += v
                nz[i] += v if name in r else default
        self.req, self.nz = req, nz
        aff = spec.get("affinity") or {}
        pa, anti = aff.get("podAffinity") or {}, aff.get("podAntiAffinity") or {}
        # (weight, namespaces, selector, key) of the preferred terms
        self.pref = [(sign * float(wt.get("weight", 0)),)
                     + self._term(wt.get("term") or {})
                     for sign, terms in ((1.0, pa), (-1.0, anti))
                     for wt in terms.get("preferred") or []]
        # (selector, key) of the required terms, as harness/check.py reads
        # them: labels alone, any namespace
        self.req_aff = [(_selector(t), t.get("topologyKey", ""))
                        for t in pa.get("required") or []]
        self.req_anti = [(_selector(t), t.get("topologyKey", ""))
                         for t in anti.get("required") or []]
        # the terms this pod carries once bound: (kind, namespaces,
        # selector, key) -> weight
        self.carried: dict = {}
        for kind, terms, weighted in (("aff_pref", pa.get("preferred"), True),
                                      ("anti_pref", anti.get("preferred"), True),
                                      ("aff_req", pa.get("required"), False)):
            for t in terms or []:
                f = (kind,) + self._term((t.get("term") or {}) if weighted else t)
                w = float(t.get("weight", 0)) if weighted else 1.0
                self.carried[f] = self.carried.get(f, 0.0) + w
        self.signature = (self.ns, frozenset(self.labels.items()),
                          tuple(req), tuple(nz), tuple(self.pref),
                          tuple(self.req_aff), tuple(self.req_anti))

    def _term(self, term: dict) -> tuple:
        nss = frozenset(term.get("namespaces") or [self.ns])
        return nss, _selector(term), term.get("topologyKey", "")

    def matches(self, nss, selector) -> bool:
        return ((nss is None or self.ns in nss)
                and all(self.labels.get(k) == v for k, v in selector))


class _State:
    """The cluster after the first `length` binds of the replay."""

    def __init__(self, n: int):
        self.length = 0
        self.nz = np.zeros((2, n))    # non-zero requests: what scores read
        self.used = np.zeros((2, n))  # requests: what filters read
        self.pods = np.zeros(n)
        self.counts: dict = {}        # feature -> [domains] float
        self.parts: dict = {}         # pod signature -> (_Pod, its parts)

    def copy(self) -> "_State":
        s = _State.__new__(_State)
        s.length, s.nz, s.used = self.length, self.nz.copy(), self.used.copy()
        s.pods = self.pods.copy()
        s.counts = {f: c.copy() for f, c in self.counts.items()}
        s.parts = {sig: (pod, [a.copy() for a in parts])
                   for sig, (pod, parts) in self.parts.items()}
        return s


class _Replay:
    """The binds so far, the state after all of them (`now`) and the anchor."""

    def __init__(self, cluster):
        names = list(cluster.nodes)
        self.index = {name: i for i, name in enumerate(names)}
        nd = [cluster.nodes[name] for name in names]
        self.labels = [d["labels"] for d in nd]
        # cpu (millicores) and memory (bytes): as filters read them, and
        # as a fraction's denominator (never 0)
        self.cap = np.array([[d["cpu"] for d in nd], [d["memory"] for d in nd]],
                            dtype=float)
        self.alloc = np.maximum(self.cap, 1.0)
        self.cap_pods = np.array([d["pods"] for d in nd], dtype=float)
        self.schedulable = ~np.array([d["unschedulable"] for d in nd])
        # topology key -> ([N] domain, -1 without the key; domains): a
        # count has one slot more, always 0, which domain -1 reads
        self.doms: dict = {}
        self.binds: list = []         # (_Pod, node row)
        self.term_features: set = set()
        self.now = _State(len(names))
        self.anchor = _State(len(names))
        self.floor = 0                # the oldest state the anchor may be
        self.refused = 0
        # a _Pod reads nothing of a manifest's name: one per template;
        # and the last manifest read, which bind() reads after why_not()
        self.parsed: dict = {}
        self.last = (None, None)

    def pod(self, manifest: dict) -> _Pod:
        if self.last[0] is manifest:
            return self.last[1]
        meta = manifest.get("metadata") or {}
        key = repr((meta.get("namespace"), meta.get("labels"),
                    manifest.get("spec")))
        got = self.parsed.get(key)
        if got is None:
            got = self.parsed[key] = _Pod(manifest)
        self.last = (manifest, got)
        return got

    def dom(self, key: str):
        """([N] domain of each node, -1 without the key; domains; whether
        every domain is one node, as a hostname's are)."""
        got = self.doms.get(key)
        if got is None:
            values: dict = {}
            d = np.array([values.setdefault(lab[key], len(values))
                          if key in lab else -1 for lab in self.labels])
            got = self.doms[key] = (d, max(len(values), 1),
                                    len(values) == int(np.sum(d >= 0)))
        return got

    def _adds(self, feature, pod: _Pod) -> float:
        if feature[0] == "sel":
            _, nss, sel, _key = feature
            return 1.0 if pod.matches(nss, sel) else 0.0
        return pod.carried.get(feature, 0.0)

    def counts(self, state: _State, feature) -> np.ndarray:
        """[domains] of `feature` under `state`: ("sel", namespaces or
        None, selector, key), the pods that match; (kind, namespaces,
        selector, key), the weight of the terms of that kind they carry."""
        got = state.counts.get(feature)
        if got is None:
            d, n_dom, _ = self.dom(feature[-1])
            got = np.zeros(n_dom + 1)
            for pod, row in self.binds[: state.length]:
                w = self._adds(feature, pod)
                if w and d[row] >= 0:
                    got[d[row]] += w
            state.counts[feature] = got
        return got

    def per_node(self, state: _State, feature) -> np.ndarray:
        """[N] the counts of `feature` in each node's domain under `state`."""
        return self.counts(state, feature)[self.dom(feature[-1])[0]]

    def advance(self, state: _State) -> None:
        """`state` one bind further, with the parts it holds."""
        pod, row = self.binds[state.length]
        state.length += 1
        state.nz[:, row] += pod.nz
        state.used[:, row] += pod.req
        state.pods[row] += 1
        for feature, c in state.counts.items():
            w = self._adds(feature, pod)
            if w:
                d = self.dom(feature[-1])[0][row]
                if d >= 0:
                    c[d] += w
        for p, parts in state.parts.values():
            self._move(state, p, parts, pod, row)

    def feasible(self, state: _State, pod: _Pod) -> np.ndarray:
        ok = (self.schedulable & (state.pods + 1 <= self.cap_pods)
              & (state.used[0] + pod.req[0] <= self.cap[0])
              & (state.used[1] + pod.req[1] <= self.cap[1]))
        for sel, key in pod.req_aff:
            c = self.per_node(state, ("sel", None, sel, key))
            alone = (pod.matches(None, sel)
                     and not self.counts(state, ("sel", None, sel, key)).any())
            ok &= (self.dom(key)[0] >= 0) & ((c > 0) | alone)
        for sel, key in pod.req_anti:
            ok &= ~((self.dom(key)[0] >= 0)
                    & (self.per_node(state, ("sel", None, sel, key)) > 0))
        return ok

    def _resource(self, state: _State, pod: _Pod, rows):
        """The constants, LeastAllocated ((1 - cpu) + (1 - mem)) x 50 and
        BalancedAllocation (1 - |cpu - mem|) x 100 on `rows`."""
        cpu = np.minimum((state.nz[0, rows] + pod.nz[0]) / self.alloc[0, rows], 1.0)
        mem = np.minimum((state.nz[1, rows] + pod.nz[1]) / self.alloc[1, rows], 1.0)
        return (CONSTANT + 200.0) - 50.0 * (cpu + mem) - 100.0 * np.abs(cpu - mem)

    def parts(self, state: _State, pod: _Pod) -> list:
        """[N] x 3 of the pod under `state`: the score but InterPodAffinity,
        InterPodAffinity's raw sum, and where it is feasible (the nodes it
        is normalised over)."""
        got = state.parts.get(pod.signature)
        if got is None:
            raw = np.zeros(len(self.labels))
            for w, nss, sel, key in pod.pref:
                raw += w * self.per_node(state, ("sel", nss, sel, key))
            for f in self.term_features:
                if pod.matches(f[1], f[2]):
                    raw += _SIGN[f[0]] * self.per_node(state, f)
            got = state.parts[pod.signature] = (pod, [
                self._resource(state, pod, slice(None)), raw,
                self.feasible(state, pod)])
        return got[1]

    @staticmethod
    def total(parts) -> np.ndarray:
        rest, raw, feas = parts
        hi = np.max(raw, where=feas, initial=0.0)
        lo = np.min(raw, where=feas, initial=0.0)
        return rest + (raw - lo) * (100.0 / (hi - lo)) if hi > lo else rest

    def _move(self, state: _State, pod: _Pod, parts: list, q: _Pod, row: int):
        """The pod's `parts` after `q` was bound on `row` (`state` already
        counts it): only that node's resources, and its domains' raw
        sums, move."""
        rest, raw, feas = parts
        rest[row] = self._resource(state, pod, row)
        moves = [(w, key) for w, nss, sel, key in pod.pref if q.matches(nss, sel)]
        moves += [(_SIGN[f[0]] * w, f[-1]) for f, w in q.carried.items()
                  if pod.matches(f[1], f[2])]
        for w, key in moves:
            d, _, singletons = self.dom(key)
            if d[row] >= 0:
                if singletons:
                    raw[row] += w
                else:
                    raw[d == d[row]] += w
        if pod.req_aff or pod.req_anti:
            parts[2] = self.feasible(state, pod)
        else:
            feas[row] = (self.schedulable[row]
                         and state.pods[row] + 1 <= self.cap_pods[row]
                         and state.used[0, row] + pod.req[0] <= self.cap[0, row]
                         and state.used[1, row] + pod.req[1] <= self.cap[1, row])

    def admits(self, parts, row: int, feas_now) -> bool:
        s = self.total(parts)
        return s[row] >= np.max(s, where=feas_now, initial=-np.inf) - EPSILON


def _replay(cluster) -> _Replay:
    r = cluster.rule_state.get(NAME)
    if r is None:
        r = cluster.rule_state[NAME] = _Replay(cluster)
    return r


def why_not(manifest: dict, node_name: str, cluster):
    """None if `node_name` scores within EPSILON of the best feasible
    node under some state the pod's launch may have been scored on."""
    r = _replay(cluster)
    pod, row = r.pod(manifest), r.index[node_name]
    if pod.created_bound:
        return None
    t = r.now.length
    while r.anchor.length < max(r.floor, t - (LAUNCH_MAX - 1)):
        r.advance(r.anchor)
    feas = r.parts(r.now, pod)[2]
    if not feas[row] or r.admits(r.parts(r.anchor, pod), row, feas):
        return None
    if r.refused < SEARCHED_REFUSALS:
        state = r.anchor.copy()
        while state.length < t:
            r.advance(state)
            if r.admits(r.parts(state, pod), row, feas):
                r.anchor = state
                return None
    r.refused += 1
    s = r.total(r.parts(r.anchor, pod))
    best = int(np.flatnonzero(feas)[np.argmax(s[feas])])
    names = list(r.index)
    return (f"{NAME}: {node_name} scores {s[row]:.3f} and {names[best]} "
            f"{s[best]:.3f} under state {r.anchor.length} of {t}, and no "
            f"later state admits {node_name}")


def bind(manifest: dict, node_name: str, cluster) -> None:
    r = _replay(cluster)
    row = r.index.get(node_name)
    if row is None:
        return
    pod = r.pod(manifest)
    r.binds.append((pod, row))
    r.term_features.update(pod.carried)
    r.advance(r.now)
    if pod.created_bound:
        r.floor = r.now.length


def scores(manifest: dict, cluster) -> dict:
    """node name -> the pod's score under the binds replayed so far, on
    every node feasible for it now."""
    r = _replay(cluster)
    pod = r.pod(manifest)
    s, feas = r.total(r.parts(r.now, pod)), r.feasible(r.now, pod)
    return {name: float(s[i]) for name, i in r.index.items() if feas[i]}
