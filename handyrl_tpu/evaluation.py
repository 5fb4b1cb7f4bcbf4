"""Evaluation: online eval matches, offline eval driver, network battles.

Capability parity with the reference evaluation layer
(/root/reference/handyrl/evaluation.py): the online Evaluator used by
workers during training, the multiprocess offline driver behind
``--eval`` (two-player seats equalized first/second), and the network
battle mode where a server hosts the env and remote clients drive
agents over TCP via the env's ``diff_info``/``update`` delta-sync
protocol on port 9876.

Protocol surfaces (fixed): the RPC verbs ``update / outcome / action /
observe / quit``, the network port, and the result dict
``{args, result, opponent}`` consumed by the learner.  The match
drivers, seat scheduling, and result aggregation are organized
framework-side here (ResultTable, _seat_plan).
"""

import multiprocessing as mp
import random
import time

from .agent import Agent, RandomAgent, RuleBasedAgent
from .connection import (
    accept_socket_connections,
    open_socket_connection,
)
from .environment import make_env, prepare_env
from .models import TPUModel

NETWORK_PORT = 9876


# ---------------------------------------------------------------------
# network battle plumbing
# ---------------------------------------------------------------------

class NetworkAgentClient:
    """Client side of a network battle: owns a real agent plus a mirror
    env kept in sync by the server's diff stream, and answers RPC verbs
    until told to quit."""

    def __init__(self, agent, env, conn):
        self.conn = conn
        self.agent = agent
        self.env = env

    def _on_update(self, data, reset):
        self.env.update(data, reset)
        print(self.env)
        if reset:
            # new game: recurrent agents must drop the old hidden state
            self.agent.reset(self.env, show=True)
        return None

    def _on_action(self, player):
        action = self.agent.action(self.env, player, show=True)
        return self.env.action2str(action, player)

    def _on_observe(self, player):
        return self.agent.observe(self.env, player, show=True)

    def run(self):
        while True:
            try:
                # jaxlint: disable=unbounded-recv -- server-driven session: the server sends "quit" at series end, and a dead server raises here
                verb, payload = self.conn.recv()
            except (ConnectionResetError, EOFError):
                break
            if verb == "quit":
                break
            if verb == "outcome":
                print(f"outcome = {payload[0]}")
                reply = None
            elif verb == "update":
                reply = self._on_update(*payload)
            elif verb == "action":
                reply = self._on_action(*payload)
            elif verb == "observe":
                reply = self._on_observe(*payload)
            else:
                reply = getattr(self.env, verb)(*payload)
            self.conn.send(reply)


class NetworkAgent:
    """Server-side stub forwarding agent verbs to a remote client."""

    def __init__(self, conn):
        self.conn = conn

    def _call(self, verb, *payload):
        self.conn.send((verb, list(payload)))
        # jaxlint: disable=unbounded-recv -- request/reply over a live match connection; a dead client raises ConnectionError instead of blocking
        return self.conn.recv()

    def update(self, data, reset):
        return self._call("update", data, reset)

    def outcome(self, outcome):
        return self._call("outcome", outcome)

    def action(self, player):
        return self._call("action", player)

    def observe(self, player):
        return self._call("observe", player)

    def quit(self):
        """End the client's session.  Fire-and-forget by protocol: the
        client breaks its recv loop without replying, so this must NOT
        wait for one (a ``send_recv`` here would wedge forever — the
        exact shape commlint's reply-mismatch rule exists for)."""
        try:
            self.conn.send(("quit", []))
        except (ConnectionError, OSError):
            pass  # client already gone: the session is over either way


# ---------------------------------------------------------------------
# match drivers
# ---------------------------------------------------------------------

def exec_match(env, agents, critic=None, show=False, game_args={}):
    """One match on a shared env instance; returns per-player outcome
    or None on env failure."""
    if env.reset(game_args):
        return None
    for agent in agents.values():
        agent.reset(env, show=show)

    while not env.terminal():
        if show:
            print(env)
        on_turn, watching = env.turns(), env.observers()
        actions = {
            p: agent.action(env, p, show=show)
            for p, agent in agents.items() if p in on_turn
        }
        for p, agent in agents.items():
            if p in watching and p not in on_turn:
                agent.observe(env, p, show=show)
        if env.step(actions):
            return None
        if show and critic is not None:
            print(f"cv = {critic.observe(env, None, show=False)}")

    if show:
        print(env)
        print(f"final outcome = {env.outcome()}")
    return env.outcome()


def exec_network_match(env, network_agents, critic=None, game_args={}):
    """One match whose agents live on remote clients, kept in sync by
    the env's diff protocol."""

    def broadcast_state(reset):
        for p, agent in network_agents.items():
            agent.update(env.diff_info(p), reset)

    if env.reset(game_args):
        return None
    broadcast_state(reset=True)

    while not env.terminal():
        on_turn, watching = env.turns(), env.observers()
        actions = {}
        for p, agent in network_agents.items():
            if p in on_turn:
                actions[p] = env.str2action(agent.action(p), p)
            elif p in watching:
                agent.observe(p)
        if env.step(actions):
            return None
        broadcast_state(reset=False)

    outcome = env.outcome()
    for p, agent in network_agents.items():
        agent.outcome(outcome[p])
    return outcome


# ---------------------------------------------------------------------
# opponents + online evaluator
# ---------------------------------------------------------------------

def build_agent(raw, env=None):
    """Instantiate a named opponent: 'random', 'rulebase[-key]'."""
    if raw == "random":
        return RandomAgent()
    if raw.startswith("rulebase"):
        key = raw.split("-")[1] if "-" in raw else None
        return RuleBasedAgent(key)
    return None


def configured_opponents(args, prefer_cli=False):
    """Opponent pool from config; resolves both the training-side
    ``eval.opponent`` and the CLI-side ``eval_args.opponent`` spelling
    in one place.  ``prefer_cli`` flips the priority for the ``--eval``
    entry point, whose traditional key is ``eval_args``."""
    keys = ["eval", "eval_args"]
    if prefer_cli:
        keys.reverse()
    raw = (
        args.get(keys[0], {}).get("opponent")
        or args.get(keys[1], {}).get("opponent")
        or ["random"]
    )
    return raw if isinstance(raw, list) else [raw]


class Evaluator:
    """Online evaluation during training: the current model in the
    trained seats vs a configured opponent in the rest."""

    def __init__(self, env, args):
        self.env = env
        self.args = args
        self.opponents = configured_opponents(args)

    def _seat(self, model, opponent):
        if model is None:
            return build_agent(opponent, self.env) or RandomAgent()
        return Agent(model, observation=self.args["observation"])

    def execute(self, models, args):
        opponent = random.choice(self.opponents)
        agents = {p: self._seat(m, opponent) for p, m in models.items()}
        outcome = exec_match(self.env, agents)
        if outcome is None:
            print("None episode in evaluation!")
            return None
        return {"args": args, "result": outcome, "opponent": opponent}


# ---------------------------------------------------------------------
# offline evaluation farm
# ---------------------------------------------------------------------

def wp_func(results):
    """Win rate over an outcome histogram (draws count half)."""
    games = sum(results.values())
    if games == 0:
        return 0.0
    wins = sum(n for outcome, n in results.items() if outcome > 0)
    draws = sum(n for outcome, n in results.items() if outcome == 0)
    return (wins + draws / 2) / games


class ResultTable:
    """Outcome histograms per agent, split by seat pattern."""

    def __init__(self, num_agents):
        self.by_pattern = [{} for _ in range(num_agents)]
        self.overall = [{} for _ in range(num_agents)]

    def add(self, players, agent_ids, pattern, outcome):
        for seat, player in enumerate(players):
            agent_id = agent_ids[seat]
            oc = outcome[player]
            histogram = self.by_pattern[agent_id].setdefault(pattern, {})
            histogram[oc] = histogram.get(oc, 0) + 1
            self.overall[agent_id][oc] = self.overall[agent_id].get(oc, 0) + 1

    def report(self):
        for agent_id, patterns in enumerate(self.by_pattern):
            print(f"agent {agent_id}")
            for pattern, histogram in patterns.items():
                print(f"    pattern {pattern}: "
                      f"win rate = {wp_func(histogram):.3f} "
                      f"({sum(histogram.values())} games)")
        for agent_id, histogram in enumerate(self.overall):
            print(f"agent {agent_id}: win rate = {wp_func(histogram):.3f}")


def _seat_plan(num_agents, num_games, pattern):
    """Yield (agent_ids, pattern_tag) per game.  Two-agent series play
    half the games with each agent moving first; larger pools are
    shuffled per game."""
    for g in range(num_games):
        if num_agents == 2:
            first = 0 if g < (num_games + 1) // 2 else 1
            tag = f"{pattern}_{'first' if first == 0 else 'second'}"
            yield [first, 1 - first], tag
        else:
            yield random.sample(range(num_agents), num_agents), pattern


def _match_series_child(agents, critic, env_args, index, in_queue,
                        out_queue, seed, show=False):
    """One eval process: drain the job queue, play, report outcomes."""
    from .connection import force_cpu_jax

    force_cpu_jax()
    random.seed(seed + index)
    env = make_env({**env_args, "id": index})
    while True:
        # jaxlint: disable=unbounded-recv -- the parent enqueues one None sentinel per child after the jobs, so this drain always terminates
        job = in_queue.get()
        if job is None:
            break
        game_index, agent_ids, pattern, game_args = job
        print(f"*** Game {game_index} ***")
        seats = {
            env.players()[seat]: agents[agent_id]
            for seat, agent_id in enumerate(agent_ids)
        }
        remote = isinstance(next(iter(seats.values())), NetworkAgent)
        if remote:
            outcome = exec_network_match(env, seats, critic,
                                         game_args=game_args)
        else:
            outcome = exec_match(env, seats, critic, show=show,
                                 game_args=game_args)
        out_queue.put((pattern, agent_ids, outcome))
    # series over: release remote clients so they exit their recv
    # loops promptly instead of wedging until process teardown (the
    # "quit" verb was handled client-side but never sent — commlint's
    # dead-handler found the missing half of the protocol)
    for agent in agents:
        if isinstance(agent, NetworkAgent):
            agent.quit()
    out_queue.put(None)


def evaluate_mp(env, agents, critic, env_args, args_patterns, num_process,
                num_games, seed):
    """Offline evaluation farm: ``num_process`` processes play
    ``num_games`` per pattern; outcomes land in the returned (and
    printed) ResultTable."""
    from .connection import _mp

    in_queue, out_queue = _mp.Queue(), _mp.Queue()
    print("total games = %d" % (len(args_patterns) * num_games))
    time.sleep(0.1)

    jobs = 0
    for pattern, game_args in args_patterns.items():
        for agent_ids, tag in _seat_plan(len(agents), num_games, pattern):
            in_queue.put((jobs, agent_ids, tag, game_args))
            jobs += 1

    network_mode = agents[0] is None
    if network_mode:
        per_process_agents = network_match_acception(
            num_process, env_args, len(agents), NETWORK_PORT)
    else:
        per_process_agents = [agents] * num_process

    for i in range(num_process):
        in_queue.put(None)
        child_args = (per_process_agents[i], critic, env_args, i,
                      in_queue, out_queue, seed)
        if num_process > 1:
            _mp.Process(target=_match_series_child, args=child_args,
                        daemon=True).start()
            if network_mode:
                for agent in per_process_agents[i]:
                    agent.conn.close()
        else:
            _match_series_child(*child_args, show=True)

    table = ResultTable(len(agents))
    live_children = num_process
    while live_children > 0:
        # jaxlint: disable=unbounded-recv -- every child posts a None sentinel on exit (even after env failures), so this loop always drains
        item = out_queue.get()
        if item is None:
            live_children -= 1
            continue
        pattern, agent_ids, outcome = item
        if outcome is not None:
            table.add(env.players(), agent_ids, pattern, outcome)
    table.report()
    return table


def network_match_acception(n, env_args, num_agents, port):
    """Accept ``n * num_agents`` client connections, grouping them in
    arrival order into per-match agent lists.  Every accepted client is
    sent the env args (its handshake to start mirroring the env)."""
    matches = []
    current = []
    for conn in accept_socket_connections(port):
        if conn is None:
            continue
        conn.send(env_args)
        current.append(conn)
        if len(current) == num_agents:
            matches.append([NetworkAgent(c) for c in current])
            current = []
        if len(matches) >= n:
            break
    return matches


# ---------------------------------------------------------------------
# model loading + CLI entry points
# ---------------------------------------------------------------------

def load_model(model_path, env):
    """Load a saved checkpoint (.ckpt pickle, exported .npz, or an
    ``.onnx`` artifact run by the bundled numpy ONNX runtime) into an
    evaluation model."""
    import pickle

    if model_path.endswith(".onnx"):
        # same capability as the reference's onnxruntime path
        # (/root/reference/handyrl/evaluation.py:287-365,356-365):
        # third-party or exported graphs play through --eval
        from .interop.onnx_run import OnnxModel

        return OnnxModel(model_path)
    model = TPUModel(env.net())
    if model_path.endswith(".npz"):
        import numpy as np

        from .utils.tree import unflatten_params

        archive = np.load(model_path)
        model.params = unflatten_params({
            key: archive[key] for key in archive.files
            if key != "__header__"
        })
        return model
    with open(model_path, "rb") as f:
        state = pickle.load(f)
    params = state["params"] if isinstance(state, dict) and "params" in state \
        else state
    model.params = params
    return model


def _resolve_agent(raw, env):
    """A CLI agent spec: a named opponent or a checkpoint path."""
    agent = build_agent(raw, env)
    if agent is None:
        agent = Agent(load_model(raw, env))
    return agent


def eval_main(args, argv):
    env_args = args["env_args"]
    prepare_env(env_args)
    env = make_env(env_args)

    model_path = argv[0] if len(argv) >= 1 else "models/latest.ckpt"
    num_games = int(argv[1]) if len(argv) >= 2 else 100
    num_process = int(argv[2]) if len(argv) >= 3 else 1

    main_agent = _resolve_agent(model_path, env)
    print(f"evaluated files = {model_path}")

    seed = random.randrange(1 << 31)
    print(f"seed = {seed}")
    opponent = configured_opponents(args, prefer_cli=True)[0]
    agents = [main_agent] + [
        build_agent(opponent, env) or RandomAgent()
        for _ in range(len(env.players()) - 1)
    ]
    return evaluate_mp(env, agents, None, env_args, {"default": {}},
                       num_process, num_games, seed)


def eval_server_main(args, argv):
    print("network match server mode")
    env_args = args["env_args"]
    prepare_env(env_args)
    env = make_env(env_args)

    num_games = int(argv[0]) if len(argv) >= 1 else 100
    num_process = int(argv[1]) if len(argv) >= 2 else 1

    seed = random.randrange(1 << 31)
    print(f"seed = {seed}")
    evaluate_mp(env, [None] * len(env.players()), None, env_args,
                {"default": {}}, num_process, num_games, seed)


def client_mp_child(env_args, model_path, conn):
    env = make_env(env_args)
    model = load_model(model_path, env)
    NetworkAgentClient(Agent(model), env, conn).run()


def eval_client_main(args, argv):
    print("network match client mode")
    from .connection import _mp

    procs, conns = [], []
    while True:
        try:
            host = argv[1] if len(argv) >= 2 else "localhost"
            conn = open_socket_connection(host, NETWORK_PORT)
            # jaxlint: disable=unbounded-recv -- one-shot startup handshake: the server sends env_args immediately on accept, and a dead server raises out of the session loop
            env_args = conn.recv()
        except (EOFError, ConnectionError, OSError):
            break

        model_path = argv[0] if len(argv) >= 1 else "models/latest.ckpt"
        p = _mp.Process(target=client_mp_child,
                        args=(env_args, model_path, conn), daemon=True)
        p.start()
        procs.append(p)
        # keep our copy open: spawned children receive the socket via
        # the resource sharer, which needs the parent fd alive
        conns.append(conn)
    for p in procs:
        p.join()
