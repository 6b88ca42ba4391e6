"""League training (league.py): PFSP opponent sampling over the model
registry, the persistent Elo RatingBook, the rating-gated promotion path,
GC pinning of pool members, ledger re-issue stickiness of server-stamped
opponent assignments, and the server-stamped opponent override on the
worker-mode Evaluator — plus the ConnectX adapter that gives the league a
fourth environment. The slow test at the bottom is the full e2e: a real
TCP fleet with league.enabled, a SIGTERM/restart that preserves ratings,
and a promotion landing in the registry manifest."""

import copy
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from handyrl_tpu import league
from handyrl_tpu.config import apply_defaults
from handyrl_tpu.environment import make_env
from handyrl_tpu.fault import TaskLedger
from handyrl_tpu.model import ModelWrapper
from handyrl_tpu.serving.registry import ModelRegistry
from handyrl_tpu.utils.fs import checksummed_write_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ttt_wrapper(seed=7):
    env = make_env({'env': 'TicTacToe'})
    env.reset()
    w = ModelWrapper(env.net(), seed=seed)
    w.ensure_params(env.observation(0))
    return env, w


# ---------------------------------------------------------------------------
# PFSP weighting curves


def test_pfsp_variance_prefers_even_matches():
    w = league.pfsp_weights([0.0, 0.5, 1.0], curve='variance')
    assert w.shape == (3,)
    assert w[1] > w[0] and w[1] > w[2]
    assert (w > 0).all()          # the floor keeps everyone reachable


def test_pfsp_hard_prefers_strong_opponents():
    w = league.pfsp_weights([0.1, 0.5, 0.9], curve='hard', hard_exponent=2.0)
    assert w[0] > w[1] > w[2]
    # a larger exponent sharpens the preference for the hardest member
    sharp = league.pfsp_weights([0.1, 0.5, 0.9], curve='hard',
                                hard_exponent=4.0)
    assert sharp[0] / sharp[1] > w[0] / w[1]


def test_pfsp_uniform_and_unknown_curve():
    w = league.pfsp_weights([0.0, 0.3, 1.0], curve='uniform')
    assert np.allclose(w, w[0])
    with pytest.raises(ValueError):
        league.pfsp_weights([0.5], curve='nope')


def test_member_name_round_trip():
    assert league.member_name('default', 3) == 'default@3'
    assert league.split_member('default@3') == ('default', '3')
    assert league.split_member('a@b@c') == ('a@b', 'c')
    assert league.split_member('random') == (None, None)


# ---------------------------------------------------------------------------
# sampling: deterministic per (seed, sample_key), audited seed machinery


def _pool_with_versions(root, versions, **overrides):
    reg = ModelRegistry(str(root))
    _, w = _ttt_wrapper()
    for v in versions:
        path = os.path.join(str(root), '%d.ckpt' % v)
        checksummed_write_bytes(path, w.params_bytes())
        reg.publish('default', path=path, architecture='SimpleConv2dModel',
                    version=v, promote=(v == versions[0]))
    args = dict(apply_defaults({'env_args': {'env': 'TicTacToe'}})
                ['train_args']['league'])
    args.update(overrides)
    pool = league.LeaguePool(args, 'default')
    pool.refresh(reg)
    return pool, reg


def test_sample_opponent_is_deterministic_and_diverse(tmp_path):
    pool, _ = _pool_with_versions(tmp_path, [1, 2], self_play_rate=0.0,
                                  curve='uniform')
    book = league.RatingBook()
    draws = [pool.sample_opponent(11, k, book) for k in range(200)]
    again = [pool.sample_opponent(11, k, book) for k in range(200)]
    assert draws == again                       # pure function of the task
    assert None not in draws                    # self_play_rate 0: all member
    assert {'default@1', 'default@2'} <= set(draws)
    # a different base seed is a different (still deterministic) sequence
    other = [pool.sample_opponent(12, k, book) for k in range(200)]
    assert other != draws


def test_sample_opponent_self_play_share(tmp_path):
    pool, _ = _pool_with_versions(tmp_path, [1], self_play_rate=1.0)
    book = league.RatingBook()
    assert all(pool.sample_opponent(0, k, book) is None for k in range(50))


def test_rating_opponent_round_robin_covers_roster(tmp_path):
    pool, _ = _pool_with_versions(tmp_path, [1, 2])
    roster = pool.roster()
    assert 'random' in roster
    seen = [pool.rating_opponent(i) for i in range(2 * len(roster))]
    assert seen[:len(roster)] == roster
    assert seen == roster + roster              # coverage, not exploration


def test_member_model_ids(tmp_path):
    pool, _ = _pool_with_versions(tmp_path, [1, 2])
    assert pool.member_model_id('default@2') == 2
    assert pool.member_model_id(league.RANDOM_ANCHOR) == 0
    assert pool.member_model_id('rulebase') is None


def test_refresh_keeps_champion_outside_member_window(tmp_path):
    # max_members 2 would drop v1 by recency, but v1 is the champion
    pool, _ = _pool_with_versions(tmp_path, [1, 2, 3, 4], max_members=2)
    assert pool.champion == 'default@1'
    assert 'default@1' in pool.members()
    assert {'default@3', 'default@4'} <= set(pool.members())
    assert 'default@2' not in pool.members()


# ---------------------------------------------------------------------------
# Elo rating book


def test_elo_win_raises_learner_and_mirrors_member():
    book = league.RatingBook(track_sigma=False, k_factor=32.0)
    book.record('m', 1.0)
    assert book.rating(league.LEARNER) == pytest.approx(1216.0)
    assert book.rating('m') == pytest.approx(1184.0)   # mirrored delta
    book.record('m', 0.0)
    # the loss moves more than the win did (learner was favored)
    assert book.rating(league.LEARNER) < 1200.0
    assert book.win_rate('m') == pytest.approx(0.5)
    assert book.games('m') == 2
    assert book.games_since_promote == 2


def test_sigma_shrinks_with_games_and_scales_k():
    book = league.RatingBook(track_sigma=True, initial_sigma=200.0,
                             min_sigma=50.0)
    for _ in range(100):
        book.record('m', 1.0)
    e = book.entry('m')
    assert e['sigma'] == pytest.approx(
        max(50.0, 200.0 / np.sqrt(1.0 + 100 / 8.0)))
    assert e['sigma'] < 200.0
    # a settled entry moves less per game than a fresh one
    settled = abs(book._k(e) - book.k_factor)
    assert book._k(e) < book.k_factor
    assert book._k({'sigma': 200.0}) == book.k_factor
    assert settled > 0


def test_journal_round_trip_is_bit_identical(tmp_path):
    path = str(tmp_path / 'ratings.json')
    book = league.RatingBook()
    for i in range(17):
        book.record('default@%d' % (i % 3), (i % 5) / 4.0)
    book.note_promotion()
    book.record('random', 0.5)
    book.save(path)
    raw = open(path, 'rb').read()

    clone = league.RatingBook()
    assert clone.load(path)
    clone.save(str(tmp_path / 'again.json'))
    assert open(str(tmp_path / 'again.json'), 'rb').read() == raw

    # the restored book reproduces subsequent updates bit-identically
    book.record('default@1', 1.0)
    clone.record('default@1', 1.0)
    assert clone.to_state() == book.to_state()


def test_journal_load_missing_or_torn(tmp_path):
    book = league.RatingBook()
    assert not book.load(str(tmp_path / 'absent.json'))
    torn = tmp_path / 'torn.json'
    torn.write_text('{"entries": {tor')
    assert not book.load(str(torn))
    assert book.names() == []                   # fresh book unharmed


# ---------------------------------------------------------------------------
# provisional members: the gateway's external players


def test_provisional_member_rating_flow():
    """``seed_provisional`` creates an unrated outsider at the learner's
    current rating; ``record_between`` moves BOTH sides' Elo but books
    the learner-relative PFSP (games, wins) statistics only on the
    provisional side — a rated member's PFSP curve is never polluted by
    third-party matches — and the promotion denominator never moves."""
    book = league.RatingBook(track_sigma=False, k_factor=32.0)
    book.seed('default@1', 1300.0)
    book.entry(league.LEARNER)['rating'] = 1250.0
    e = book.seed_provisional('gateway:alice')
    assert book.is_provisional('gateway:alice')
    assert e['rating'] == pytest.approx(1250.0)      # learner-seeded
    assert book.seed_provisional('gateway:alice') is e   # idempotent
    assert not book.is_provisional('default@1')
    assert not book.is_provisional('nobody')

    before = book.games_since_promote
    book.record_between('gateway:alice', 'default@1', 1.0)   # upset win
    assert book.rating('gateway:alice') > 1250.0
    assert book.rating('default@1') < 1300.0
    assert book.games('gateway:alice') == 1
    assert book.win_rate('gateway:alice') == pytest.approx(1.0)
    assert book.games('default@1') == 0              # rated side untouched
    assert book.games_since_promote == before        # gate never fed
    # the mirrored loss books on the provisional side as its own score
    book.record_between('default@1', 'gateway:alice', 1.0)
    assert book.games('gateway:alice') == 2
    assert book.win_rate('gateway:alice') == pytest.approx(0.5)


def test_provisional_flag_survives_journal_round_trip(tmp_path):
    path = str(tmp_path / 'ratings.json')
    book = league.RatingBook()
    book.seed_provisional('gateway:bob', rating=1111.0)
    book.record_between('gateway:bob', 'default@1', 0.0)
    book.save(path)
    clone = league.RatingBook()
    assert clone.load(path)
    assert clone.is_provisional('gateway:bob')
    assert not clone.is_provisional('default@1')
    assert clone.rating('gateway:bob') == book.rating('gateway:bob')
    assert clone.to_state() == book.to_state()


def test_provisional_games_never_feed_promotion_gate(tmp_path):
    """Neither ``record_between`` third-party games nor learner games
    against a provisional opponent count toward ``min_games`` — only
    learner-vs-league games can promote a champion."""
    pool, _ = _pool_with_versions(tmp_path, [1, 2], promote_margin=0.0,
                                  min_games=2)
    book = league.RatingBook()
    book.seed_provisional('gateway:bob')
    book.entry(league.LEARNER)['rating'] = 2000.0    # miles past margin
    book.record('gateway:bob', 1.0)                  # learner vs outsider
    book.record_between('gateway:bob', 'default@1', 1.0)
    assert book.games_since_promote == 0
    assert not pool.should_promote(book)             # 0 of 2 gate games
    book.record('default@1', 1.0)
    book.record('random', 1.0)
    book.entry(league.LEARNER)['rating'] = 2000.0
    assert book.games_since_promote == 2
    assert pool.should_promote(book)


# ---------------------------------------------------------------------------
# the promotion gate


def test_should_promote_requires_margin_and_games(tmp_path):
    pool, _ = _pool_with_versions(tmp_path, [1, 2], promote_margin=30.0,
                                  min_games=5)
    book = league.RatingBook()
    book.seed('default@1', 1200.0)
    book.seed(league.LEARNER, 1240.0)           # clears the margin...
    assert not pool.should_promote(book)        # ...but 0 games booked
    for _ in range(5):
        book.record('random', 0.5)
    book.entry(league.LEARNER)['rating'] = 1240.0
    assert pool.should_promote(book)
    book.entry(league.LEARNER)['rating'] = 1229.0   # inside the margin
    assert not pool.should_promote(book)
    pool.champion = None                        # headless line: bootstrap
    assert not pool.should_promote(book)        # promotion is the registry's


class _LeagueStub:
    """The REAL Learner league epoch-sync over a synthetic registry (the
    method needs only args/model_epoch and the league triple)."""

    def __init__(self, args, pool, book, journal, epoch):
        from handyrl_tpu.train import Learner
        self.args = args
        self._registry = None
        self._league = pool
        self._league_ratings = book
        self._league_journal = journal
        self._league_sampled = {}
        self.model_epoch = epoch
        self._registry_root = Learner._registry_root.__get__(self)
        self._ensure_registry = Learner._ensure_registry.__get__(self)
        self._league_epoch_sync = Learner._league_epoch_sync.__get__(self)


def test_epoch_sync_promotes_through_the_gate(tmp_path):
    root = str(tmp_path / 'models')
    os.makedirs(root)
    pool, reg = _pool_with_versions(tmp_path / 'models', [1, 2],
                                    promote_margin=10.0, min_games=3)
    journal = league.journal_path(root)
    book = league.make_rating_book(pool.args)
    stub = _LeagueStub({'model_dir': root, 'serving': {}}, pool, book,
                       journal, epoch=2)

    # learner well above the incumbent but short on games: no flip
    book.entry(league.LEARNER)['rating'] = 1300.0
    book.record('random', 1.0)
    stub._league_epoch_sync()
    assert reg.resolve('default', 'champion')[0] == '1'
    assert book.promotions == 0
    # fresh members were seeded at the learner's rating, not the cold start
    assert book.rating('default@2') == book.rating(league.LEARNER)

    for _ in range(3):
        book.record('random', 0.5)
    book.entry(league.LEARNER)['rating'] = \
        book.rating('default@1') + 10.0         # exactly the margin
    stub._league_epoch_sync()
    assert ModelRegistry(root).resolve('default', 'champion')[0] == '2'
    assert book.promotions == 1
    assert book.games_since_promote == 0
    assert pool.champion == 'default@2'
    # the journal was written atomically and reloads bit-identically
    clone = league.RatingBook()
    assert clone.load(journal)
    assert clone.to_state() == book.to_state()


def test_epoch_sync_refuses_inside_margin(tmp_path):
    root = str(tmp_path / 'models')
    os.makedirs(root)
    pool, reg = _pool_with_versions(tmp_path / 'models', [1, 2],
                                    promote_margin=50.0, min_games=1)
    book = league.make_rating_book(pool.args)
    stub = _LeagueStub({'model_dir': root, 'serving': {}}, pool, book,
                       league.journal_path(root), epoch=2)
    book.record('random', 1.0)
    book.entry(league.LEARNER)['rating'] = book.rating('default@1') + 49.0
    stub._league_epoch_sync()
    assert reg.resolve('default', 'champion')[0] == '1'
    assert book.promotions == 0


# ---------------------------------------------------------------------------
# keep_checkpoints GC pins league members


class _GcLeagueStub:
    def __init__(self, args, pool):
        from handyrl_tpu.train import Learner
        self.args = args
        self._league = pool
        self.model_path = Learner.model_path.__get__(self)
        self._gc_checkpoints = Learner._gc_checkpoints.__get__(self)
        self._registry_root = Learner._registry_root.__get__(self)


def test_gc_pins_league_member_checkpoints(tmp_path):
    from handyrl_tpu import telemetry
    model_dir = str(tmp_path / 'models')
    os.makedirs(model_dir)
    for e in (1, 2, 3, 4, 5):
        checksummed_write_bytes(os.path.join(model_dir, '%d.ckpt' % e),
                                b'ckpt-%d' % e)
    # no registry manifest: the ONLY pin is the league membership
    pool = league.LeaguePool({}, 'default')
    pool._member_paths = {
        'default@1': os.path.join(model_dir, '1.ckpt')}
    stub = _GcLeagueStub({'keep_checkpoints': 2, 'model_dir': model_dir,
                          'eval': {}, 'serving': {}}, pool)
    before = telemetry.counter('guard_ckpt_gc_pinned_total').value
    stub._gc_checkpoints()
    left = sorted(int(n.split('.')[0]) for n in os.listdir(model_dir)
                  if n.endswith('.ckpt'))
    # 4,5 kept by the window; 1 kept by the league pin; 2,3 collected
    assert left == [1, 4, 5]
    assert telemetry.counter('guard_ckpt_gc_pinned_total').value == before + 1
    # membership rotates away: the next pass collects the old member
    pool._member_paths = {}
    stub._gc_checkpoints()
    left = sorted(int(n.split('.')[0]) for n in os.listdir(model_dir)
                  if n.endswith('.ckpt'))
    assert left == [4, 5]


# ---------------------------------------------------------------------------
# ledger re-issue keeps the server-stamped opponent


def test_ledger_reissue_preserves_league_assignment():
    ledger = TaskLedger(deadline=300.0, clock=lambda: 0.0)
    role_args = {'role': 'g', 'player': [0], 'model_id': {0: 7, 1: 3},
                 'sample_key': 41, 'league_opponent': 'default@3',
                 'league_seat': 0}
    original = copy.deepcopy(role_args)
    ledger.assign(('h', 1), role_args)
    assert role_args['task_id'] == 0
    ledger.fail_endpoint(('h', 1))
    reissued = ledger.next_reissue()
    assert reissued == original                 # bit-identical replay
    assert 'task_id' not in reissued
    # rating-match 'e' stamps survive the same way
    e_args = {'role': 'e', 'player': [1], 'model_id': {0: -1, 1: -1},
              'opponent': 'rulebase', 'league_rating_match': True}
    e_orig = copy.deepcopy(e_args)
    ledger.assign(('h', 2), e_args)
    ledger.fail_endpoint(('h', 2))
    assert ledger.next_reissue() == e_orig


# ---------------------------------------------------------------------------
# worker-mode Evaluator: stamped opponents and registry:// specs


def test_evaluator_honors_server_stamped_opponent(tmp_path):
    from handyrl_tpu.evaluation import Evaluator
    env, w = _ttt_wrapper()
    ckpt = tmp_path / 'member.ckpt'
    ckpt.write_bytes(w.params_bytes())
    # the local pool says 'random'; the server-stamped task says the member
    ev = Evaluator(env, {'eval': {'opponent': ['random']}})
    rec = ev.execute({0: w, 1: None},
                     {'role': 'e', 'player': [0], 'opponent': str(ckpt),
                      'league_rating_match': True})
    assert rec is not None
    assert rec['opponent'] == str(ckpt)
    assert abs(sum(rec['result'].values())) < 1e-9
    # without the stamp the pool draw still applies
    rec = ev.execute({0: w, 1: None}, {'role': 'e', 'player': [0]})
    assert rec['opponent'] == 'random'


def test_evaluator_accepts_registry_spec_opponent(tmp_path):
    """eval.opponent entries of the form registry://root/line@sel resolve
    through the registry on the worker-mode (sequential) Evaluator."""
    from handyrl_tpu.evaluation import Evaluator, split_model_specs
    env, w = _ttt_wrapper()
    reg = ModelRegistry(str(tmp_path))
    reg.publish('default', snapshot=w.snapshot(), version=1, promote=True)
    spec = 'registry://%s/default@champion' % tmp_path
    assert split_model_specs(spec) == [spec]
    ev = Evaluator(env, {'eval': {'opponent': [spec]}})
    for seat in (0, 1):
        rec = ev.execute({seat: w, 1 - seat: None},
                         {'role': 'e', 'player': [seat]})
        assert rec is not None
        assert rec['opponent'] == spec
        assert abs(sum(rec['result'].values())) < 1e-9
    assert len(ev._opponent_cache) == 1         # resolved once, reused


# ---------------------------------------------------------------------------
# config surface


def test_config_league_block_validation():
    ok = apply_defaults({'env_args': {'env': 'TicTacToe'},
                         'train_args': {'league': {'enabled': True},
                                        'serving': {'publish': True}}})
    assert ok['train_args']['league']['curve'] == 'variance'
    with pytest.raises(AssertionError):         # league needs the registry
        apply_defaults({'env_args': {'env': 'TicTacToe'},
                        'train_args': {'league': {'enabled': True}}})
    with pytest.raises(AssertionError):
        apply_defaults({'env_args': {'env': 'TicTacToe'},
                        'train_args': {'league': {'curve': 'sideways'}}})
    with pytest.raises(AssertionError):
        apply_defaults({'env_args': {'env': 'TicTacToe'},
                        'train_args': {'league': {'anchors': ['lizard']}}})


# ---------------------------------------------------------------------------
# ConnectX: the league's fourth environment


def test_connectx_rule_based_tactics():
    env = make_env({'env': 'ConnectX'})
    env.reset()
    # O threatens a horizontal four at columns 0-3 -> win now at 3
    for col in (0, 6, 1, 6, 2, 5):
        env.play(col)
    assert env.rule_based_action(env.turn()) == 3
    env.play(3)
    assert env.terminal() and env.outcome()[0] == 1.0

    env.reset()
    # X must block O's open three (columns 0-2) at column 3
    for col in (0, 6, 1, 6, 2):
        env.play(col)
    assert env.rule_based_action(env.turn()) == 3


def test_connectx_net_and_league_config():
    env = make_env({'env': 'ConnectX'})
    env.reset()
    w = ModelWrapper(env.net())
    obs = env.observation(0)
    assert obs.shape == (3, 6, 7)
    out = w.inference(obs, None)
    assert out['policy'].shape == (7,)
    assert -1.0 <= float(out['value'][0]) <= 1.0
    # a league config over ConnectX validates end to end
    args = apply_defaults({'env_args': {'env': 'ConnectX'},
                           'train_args': {'league': {'enabled': True},
                                          'serving': {'publish': True}}})
    assert args['train_args']['league']['enabled']


# ---------------------------------------------------------------------------
# the fleet e2e: PFSP draws, restart-safe ratings, promotion in the manifest


LEARNER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.config import apply_defaults
    from handyrl_tpu.train import Learner
    raw = {'env_args': {'env': 'TicTacToe'},
           'train_args': {'batch_size': 8, 'update_episodes': 12,
                          'minimum_episodes': 12, 'epochs': 8,
                          'forward_steps': 8, 'num_batchers': 1,
                          'eval_rate': 0.3, 'seed': 11,
                          'restart_epoch': -1, 'keep_checkpoints': 3,
                          'metrics_jsonl': %(metrics)r,
                          'model_dir': %(model_dir)r,
                          'serving': {'publish': True, 'line': 'default'},
                          'league': {'enabled': True, 'self_play_rate': 0.0,
                                     'rating_match_rate': 1.0,
                                     'curve': 'uniform', 'min_games': 1,
                                     'promote_margin': 0.0}}}
    args = apply_defaults(raw)
    learner = Learner(args=args, remote=True)
    learner.run()
    print('LEARNER DONE', learner.model_epoch, flush=True)

if __name__ == '__main__':
    main()
'''

WORKER_SCRIPT = r'''
import os
os.environ['JAX_PLATFORMS'] = 'cpu'

def main():
    from handyrl_tpu.worker import worker_main
    args = {'worker_args': {'server_address': 'localhost', 'num_parallel': 2}}
    worker_main(args, [])

if __name__ == '__main__':
    main()
'''


def _spawn(path, env, log):
    return subprocess.Popen([sys.executable, str(path)], env=env,
                            stdout=log, stderr=subprocess.STDOUT)


def _stop(proc, timeout=30):
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(timeout=10)


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_league_fleet_restart_preserves_ratings_and_promotes(tmp_path):
    model_dir = str(tmp_path / 'models')
    metrics = str(tmp_path / 'metrics.jsonl')
    journal = os.path.join(model_dir, 'league_ratings.json')
    learner_py = tmp_path / 'learner.py'
    worker_py = tmp_path / 'worker.py'
    learner_py.write_text(LEARNER_SCRIPT % {'model_dir': model_dir,
                                            'metrics': metrics})
    worker_py.write_text(WORKER_SCRIPT)
    env = {**os.environ, 'JAX_PLATFORMS': 'cpu',
           'PYTHONPATH': REPO + os.pathsep + os.environ.get('PYTHONPATH', '')}

    # -- phase 1: run until a few epochs published, then SIGTERM ----------
    l1_log = open(tmp_path / 'learner1.log', 'w')
    w1_log = open(tmp_path / 'worker1.log', 'w')
    learner = _spawn(learner_py, env, l1_log)
    worker = None
    try:
        time.sleep(3)
        worker = _spawn(worker_py, env, w1_log)
        deadline = time.time() + 240
        target = os.path.join(model_dir, '3.ckpt')
        while time.time() < deadline:
            if os.path.exists(target) or learner.poll() is not None:
                break
            time.sleep(2)
        assert os.path.exists(target), 'phase 1 never reached epoch 3'
    finally:
        _stop(learner)
        if worker is not None:
            _stop(worker)

    assert os.path.exists(journal), 'no ratings journal after phase 1'
    j1_raw = open(journal, 'rb').read()
    j1 = json.loads(j1_raw)
    assert j1['entries'], 'phase 1 booked no rated games'

    # the production journal round-trips through the book bit-identically
    book = league.RatingBook()
    assert book.load(journal)
    book.save(str(tmp_path / 'roundtrip.json'))
    assert open(str(tmp_path / 'roundtrip.json'), 'rb').read() == j1_raw

    # -- phase 2: restart (auto-resume) and run to completion -------------
    l2_log = open(tmp_path / 'learner2.log', 'w')
    w2_log = open(tmp_path / 'worker2.log', 'w')
    learner = _spawn(learner_py, env, l2_log)
    worker = None
    try:
        time.sleep(3)
        worker = _spawn(worker_py, env, w2_log)
        deadline = time.time() + 240
        while time.time() < deadline:
            if learner.poll() is not None:
                break
            time.sleep(2)
    finally:
        _stop(worker if worker is not None else learner)
        _stop(learner)

    log2 = open(tmp_path / 'learner2.log').read()
    assert 'league: reloaded ratings journal' in log2, \
        'restart did not reload the ratings book'

    j2 = json.loads(open(journal, 'rb').read())
    # ratings survived the restart: nothing booked in phase 1 was lost
    assert set(j1['entries']) <= set(j2['entries'])
    for name, entry in j1['entries'].items():
        assert j2['entries'][name]['games'] >= entry['games']
    assert j2['promotions'] >= max(1, j1['promotions'])

    # the metrics stream shows PFSP drawing >= 2 distinct registry versions
    sampled = set()
    league_recs = 0
    with open(metrics) as f:
        for line in f:
            rec = json.loads(line)
            lg = rec.get('league')
            if not lg:
                continue
            league_recs += 1
            sampled.update(lg.get('opponents_sampled') or {})
            assert 'ratings' in lg and 'champion' in lg
    assert league_recs > 0, 'no league blocks in metrics_jsonl'
    versions = {m for m in sampled if '@' in m}
    assert len(versions) >= 2, \
        'PFSP sampled %r: wanted >= 2 registry versions' % (sampled,)

    # the rating-gated promotion landed in the registry manifest
    reg = ModelRegistry(model_dir)
    champ, meta = reg.resolve('default', 'champion')
    assert int(champ) >= 1 and meta['path']
    # every live member checkpoint survived retention GC (keep=3 < members)
    pool = league.LeaguePool({}, 'default')
    pool.refresh(reg)
    for path in pool.member_paths():
        assert os.path.exists(path), 'league member %s collected' % path
